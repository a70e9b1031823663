#!/usr/bin/env python3
"""Records the small trace under tests/data: two ``verify_commit`` calls
of a 256-validator set under the profiler on the chip, written in the
plain form ``lib/tracered.py`` reduces. Run on a machine with a TPU:

    python3 benchmarks/tests/record_trace.py <out.json>
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.drivers import commit_verify as drv  # noqa: E402
from benchmarks.lib import devtrace  # noqa: E402
from benchmarks.reference import commits as ref  # noqa: E402


def main(out: str) -> int:
    import jax
    from tmtpu.config.config import CryptoConfig
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.crypto import ed25519 as prog_ed
    from tmtpu.types.validator import Validator, ValidatorSet

    crypto_batch.configure(CryptoConfig())
    crypto_batch.set_default_backend("tpu")
    crypto_batch.start_backend("tpu", "record_trace")
    vals = ref.make_valset(1, 256)
    pvals = ValidatorSet([Validator(prog_ed.PubKeyEd25519(p), 1)
                          for p in vals.pubs])
    pcs = [drv._program_commit(ref.make_commit(vals, 1, k, "rec", 6), vals)
           for k in range(3)]
    drv.call_entry(pvals, "rec", pcs[0])
    tracer = devtrace.Tracer(emulated=jax.devices()[0].platform != "tpu")
    tracer.start()
    for pc in pcs[1:]:
        with jax.profiler.TraceAnnotation("bench.verify_commit"):
            print(drv.call_entry(pvals, "rec", pc))
    red = tracer.stop(keep_plain=True)
    for p in tracer.plain["planes"]:
        print("PLANE", p["name"])
        for ln in p["lines"]:
            print("  LINE", ln["name"], len(ln["events"]),
                  [e[0][:40] for e in ln["events"][:4]])
    print(json.dumps({k: v for k, v in red.items()
                      if k in ("window_s", "busy_s", "chips")}))
    with open(out, "w") as f:
        json.dump(tracer.plain, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
