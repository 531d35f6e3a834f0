"""Run one benchmark cell once:

    python3 hkvbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  Only on a TPU: elsewhere it exits non-zero
and prints no result.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the persistent compilation cache lives at a fixed path in the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
# no eviction: with a size limit, one entry left without its access-time
# file (an interrupted write) makes every later write to the cache fail
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
# a small premapped host buffer for transfers: mapping the runtime's
# default one takes 5-15 s of every start where transparent hugepages
# are off, and most of set-up's spread; the benchmark's transfers are
# small and outside the window
os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))
# the TPU runtime's logs stay in the checkout too (its default is /tmp)
if "TPU_LOG_DIR" not in os.environ:
    (ROOT / ".hkvbench" / "tpu_logs").mkdir(parents=True, exist_ok=True)
    os.environ["TPU_LOG_DIR"] = str(ROOT / ".hkvbench" / "tpu_logs")

from hkvbench import harness  # noqa: E402

# seconds the process had run before T_START (interpreter start-up)
AGE0 = harness.process_age_s() - (time.perf_counter() - T_START)

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START, max(AGE0, 0.0)))
