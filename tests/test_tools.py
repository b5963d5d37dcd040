"""Smoke coverage for the perf tooling under tools/.

The reference ships its perf story as prose (docs/benchmarks.md); this
repo ships runnable capture/analysis tools instead, so they get the same
bitrot protection as the framework: a capture smoke run on the simulated
CPU world plus a direct check of the aggregation table.
"""

import glob
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from tools import aot_step, profile_resnet  # noqa: E402


class TestProfileResnet:
    def test_capture_produces_trace(self, world, tmp_path):
        # Tiny config: the point is the capture plumbing (spmd step, warmup,
        # profiler start/stop), not the numbers.
        profile_resnet.capture("resnet50", batch=1, steps=1,
                               trace_dir=str(tmp_path), image_size=32)
        files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        assert files, "capture produced no xplane trace"
        report = profile_resnet.analyze(str(tmp_path))
        # CPU traces carry no device plane; analyze must say so, not crash.
        assert "no device plane" in report or "device step" in report

    def test_summarize_table(self):
        events = [
            ("%fusion.1 = f32[128]{0} fusion(...)", 6.0),
            ("%fusion.2 = f32[64]{0} fusion(...)", 2.0),
            ("%convolution.7 = bf16[1,8,8,64]{3,2,1,0} convolution(...)", 12.0),
        ]
        out = profile_resnet.summarize(events, n_steps=2, step_ms=10.0)
        assert "device step: 10.00 ms" in out
        # categories: convolution 12ms > fusion 8ms, per-step halved
        assert out.index("`convolution`") < out.index("`fusion`")
        assert "| 6.00 |" in out and "| 4.00 |" in out
        assert "60.0%" in out and "40.0%" in out


class TestAotStep:
    def test_half_head_fusions(self):
        """Counts the top-level fusions that hold half a rotary head:
        an output (..., T, H > 1, D/2), alone or in a tuple; not the
        (T, 1, D/2) tables, another length, a whole head, or an
        instruction that is no fusion."""
        text = "\n".join([
            "  %fusion.446 = (bf16[1,8192,32,64]{3,2,1,0:T(8,128)(2,1)}, "
            "bf16[1,8192,32,64]{3,2,1,0}) fusion(%a), kind=kLoop",
            "  %add_convert_fusion.5 = bf16[8192,4,64]{2,1,0} "
            "fusion(%b, %c), kind=kLoop",
            "  %fusion.9 = f32[1,8192,1,64]{3,2,1,0} fusion(%d)",
            "  %fusion.10 = bf16[1,4096,32,64]{3,2,1,0} fusion(%e)",
            "  %fusion.742 = bf16[1,32,8192,128]{3,2,1,0} fusion(%f)",
            "  %copy.3 = bf16[1,8192,32,64]{3,2,1,0} copy(%g)",
        ])
        assert aot_step.half_head_fusions(text, 8192, 64) == 2
        assert aot_step.half_head_fusions(text, 8192, 32) == 0
