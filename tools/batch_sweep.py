"""Device-timed ResNet-50 batch-size sweep of the EXACT bench.py step.

Host timing charges a fixed per-call dispatch cost that amortizes
differently per batch, so batch sizes are compared on the device timeline
(historical result on another installation: 64/128/256 → 2501/2734/2589
img/s — 128 stood; see docs/benchmarks.md). The step comes from
``bench.build_resnet_bench`` so the sweep can never drift from what
bench.py times. Usage: python tools/batch_sweep.py [batches...]
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import STEPS_PER_CALL, build_resnet_bench  # noqa: E402
from horovod_tpu.core import xprof  # noqa: E402

BATCHES = [int(a) for a in sys.argv[1:]] or [128, 256]

for batch in BATCHES:
    run_once, _ = build_resnet_bench(batch_per_chip=batch)
    best = xprof.timed_steps(run_once, STEPS_PER_CALL, trials=3)
    print(json.dumps({"batch": batch, "step_ms": round(best * 1e3, 2),
                      "img_s": round(batch / best, 1)}), flush=True)
