"""The benchmark's own tests (not part of the repo's tier-1 run):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider

They run the device graph on XLA:CPU at toy sizes; nothing they print is a
device number."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the toy sizes the rehearsal and the fault tests put in the two
# configurations' place; cells, traffic and metrics are BENCHMARK.json's own
TINY = {"valset10k-inproc": "benchmarks/tests/tiny/valset64-inproc.json",
        "kvstore1-sidecar": "benchmarks/tests/tiny/kvstore1-sidecar-tiny.json"}
