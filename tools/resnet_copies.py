"""List copy/slice DMA ops in the bench ResNet step's device profile —
the r5 hunt for the 6.2% copy-done/slice-done tail named in
docs/profiles/resnet50_v5e.md. Usage: python tools/resnet_copies.py"""

from __future__ import annotations

import collections
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from horovod_tpu.core import xprof


def main() -> None:
    import bench

    run_once, _ = bench.build_resnet_bench("resnet50")
    d = tempfile.mkdtemp(prefix="rn_cp_")
    jax.profiler.start_trace(d)
    run_once()
    jax.profiler.stop_trace()
    evs = xprof.slowest_plane(xprof.device_planes(d))
    agg = collections.Counter()
    for name, _, dur in evs:
        base = xprof.hlo_base(name)
        if "copy" in base or "slice" in base:
            agg[name[:150]] += dur / 1e3 / bench.STEPS_PER_CALL
    total = sum(agg.values())
    print(f"total copy/slice: {total:.2f} ms/step")
    for name, ms in agg.most_common(20):
        print(f"{ms:8.3f} ms  {name}")


if __name__ == "__main__":
    main()
