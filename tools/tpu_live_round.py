"""One LIVE 10k-validator consensus round on the chip: proposal->commit
wall time with the device doing every batched verify dispatch.

A thin caller of tmtpu/e2e/flood_round.py ``run``: one height of that
module's many-height scripted network (one running validator, built as
node/node.py builds it, + 9,999 MockPV co-signers whose ~20k votes one
relay peer hands to ``ConsensusReactor.receive``), with the validator
given the power to propose the height itself. chip_smoke.py and
tests/test_tpu_integration.py share it; height after height with the
proposals coming from peers is the benchmark cell
``valset10k.live-rounds`` (benchmarks/drivers/live_rounds.py). ``--mixed`` splits the co-signers
round-robin across ed25519 / sr25519 / secp256k1 (reference max-valset
constant: types/vote_set.go:14-19; mixed-curve valsets are the BASELINE
"Curves" row), so one commit's verify traffic dispatches to all three
curve kernels. This process is the one process on the chip; it exits
non-zero when JAX finds no TPU.

Usage: python tools/tpu_live_round.py [--co 9999] [--mixed] [--seed 0]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--co", type=int, default=9_999)
    ap.add_argument("--mixed", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=900.0)
    args = ap.parse_args()

    from tmtpu.tpu import compat

    compat.setup_compile_cache()
    dev = compat.require_tpu("tpu_live_round", allow_emulation=False)

    from tmtpu.config.config import ConsensusConfig
    from tmtpu.e2e import flood_round

    r = flood_round.run(args.co, backend="tpu", seed=args.seed,
                        mixed=args.mixed, timeout=args.timeout,
                        consensus_config=ConsensusConfig())
    per = r["lanes_dispatched"] / max(1, r["dispatches"])
    print(json.dumps({
        "metric": "live_10k_validator_round",
        "value": round(r["round_s"], 3), "unit": "s_proposal_to_commit",
        "inject_to_commit_s": round(r["inject_to_commit_s"], 3),
        "flood_sign_s": round(r["sign_s"], 1),
        "device": dev,
        "validators": r["validators"],
        "mixed_curves": r["mixed_curves"],
        "dispatches": r["dispatches"],
        "votes_per_dispatch": round(per),
        "votes_batched": r["lanes_dispatched"],
        "precommits_in_commit": r["precommits_in_commit"],
        "warmup_s": round(sum(w[3] for w in r["warmed"]), 1),
        "shapes_warmed": len(r["warmed"]),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
