import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

# tiny sizes the CPU can run: a few thousand rows at the configured width,
# short warm-up, windows of at most 128 ops, a window of a few seconds
TINY = {
    "arxiv.churn-read": {"config": {"rows": 3000, "labelled": 1611},
                         "mix": {"write_ops_per_s": 400, "read_requests_per_s": 10,
                                 "insert_pool_rows": 4096, "warmup_s": 2,
                                 "warmup_quiet_s": 0.5, "warmup_commits": 2,
                                 "warmup_max_s": 30, "warmup_read_ids": 4096,
                                 "warmup_bursts": {"insert": [1, 4], "delete": [1, 4]},
                                 "service": {"window_ops": 128, "window_ms": 50,
                                             "max_pending_ops": 2048}}},
}
