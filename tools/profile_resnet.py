"""Capture + analyze an XLA device profile of the ResNet training step.

The profile artifact behind docs/profiles/resnet50_v5e.md: runs the exact
bench.py training step under ``jax.profiler``, then aggregates the
TensorCore op timeline (the ``XLA Ops`` line of the xplane) into a
category and top-op table. Usage:

    python tools/profile_resnet.py [--model resnet50] [--batch 128]

The reference's benchmark story stops at throughput numbers
(docs/benchmarks.md:24-54); this is the per-op evidence TPU work needs.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import resnet


def capture(model_name: str, batch: int, steps: int, trace_dir: str,
            image_size: int = 224) -> None:
    hvd.init()
    cls = {"resnet50": resnet.ResNet50, "resnet101": resnet.ResNet101}[model_name]
    model = cls(num_classes=1000, dtype=jnp.bfloat16)
    variables = resnet.init_variables(model, image_size=image_size)
    loss_fn = resnet.make_loss_fn(model)
    opt = optax.sgd(0.1, momentum=0.9)

    def train_step(variables, opt_state, batch_):
        (loss, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(variables, batch_)
        grads = hvd.allreduce_gradients(grads)
        updates, opt_state = opt.update(grads, opt_state, variables)
        variables = optax.apply_updates(variables, updates)
        variables = {"params": variables["params"],
                     "batch_stats": jax.tree.map(
                         lambda t: hvd.allreduce(t), aux["batch_stats"])}
        return variables, opt_state, loss

    step = hvd.spmd(train_step, donate_argnums=(0, 1))
    vs = hvd.replicate(variables)
    os_ = hvd.replicate(opt.init(variables))
    imgs, labels = resnet.synthetic_imagenet(batch, image_size)
    # replicate (not rank_stack) so the same batch feeds every rank — the
    # tool then works unchanged on the 1-chip bench host and the simulated
    # 8-device CPU test world.
    b = hvd.replicate((imgs.astype(jnp.bfloat16), labels))
    for _ in range(3):                       # warm up + compile
        vs, os_, loss = step(vs, os_, b)
    float(np.asarray(loss)[0])
    jax.profiler.start_trace(trace_dir)
    for _ in range(steps):
        vs, os_, loss = step(vs, os_, b)
    float(np.asarray(loss)[0])
    jax.profiler.stop_trace()


def summarize(op_events, n_steps: int, step_ms: float, top: int = 15) -> str:
    """Aggregate (hlo_name, duration_ms) pairs into the category/top-op
    table — pure so the CPU test world (whose profiler emits no device
    plane) can exercise it directly."""
    cat_ms = collections.Counter()
    op_ms = collections.Counter()
    example = {}
    for name, d in op_events:
        m = re.match(r"%([a-zA-Z][a-zA-Z0-9_-]*?)[.\d]*\s*=", name)
        base = m.group(1) if m else name[:24]
        cat_ms[base] += d
        key = name.split(" = ")[0]
        op_ms[key] += d
        example[key] = name
    tot = sum(cat_ms.values())

    lines = [f"steps profiled: {n_steps}   device step: {step_ms:.2f} ms   "
             f"sync-op time/step: {tot / n_steps:.2f} ms",
             "", "| ms/step | % | op category |", "|---|---|---|"]
    for base, ms in cat_ms.most_common(12):
        lines.append(f"| {ms / n_steps:.2f} | {100 * ms / tot:.1f}% | "
                     f"`{base}` |")
    lines += ["", f"Top {top} individual ops (ms/step):", "```"]
    for key, ms in op_ms.most_common(top):
        lines.append(f"{ms / n_steps:8.3f} ms  {example[key][:100]}")
    lines.append("```")
    return "\n".join(lines)


def analyze(trace_dir: str, top: int = 15,
            n_steps_hint: int = 1) -> str:
    """``n_steps_hint``: executions in the capture window — used to
    normalize per-step figures when the xplane carries no 'Steps' line
    (otherwise the window would be misread as one step)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    device_planes = [p for p in pd.planes if p.name.startswith("/device:")]
    # Pick the first device plane that actually carries an op timeline —
    # auxiliary device planes (e.g. a TPU backend initialized by an
    # earlier test in the process) have no "XLA Ops" line (the same rule
    # core/xprof.device_planes applies).
    plane = ops_line = None
    for cand in device_planes:
        ops_line = next((ln for ln in cand.lines if ln.name == "XLA Ops"),
                        None)
        if ops_line is not None:
            plane = cand
            break
    if plane is None:
        return (f"trace captured at {path}; no device plane with an op "
                f"timeline in the xplane (CPU backend traces carry only "
                f"host threads) — run on TPU for the per-op table.")
    steps_line = next((ln for ln in plane.lines if ln.name == "Steps"),
                      None)

    def dur_ps(ev):
        return next((v for k, v in ev.stats if k == "device_duration_ps"), 0)

    op_events = [(ev.name, dur_ps(ev) / 1e9) for ev in ops_line.events]
    if steps_line is not None and list(steps_line.events):
        step_events = list(steps_line.events)
        n_steps = len(step_events)
        step_ms = sum(dur_ps(e) for e in step_events) / 1e9 / n_steps
    else:  # no Steps annotation: normalize by the known execution count
        n_steps = max(1, n_steps_hint)
        step_ms = sum(ms for _, ms in op_events) / n_steps
    return summarize(op_events, n_steps, step_ms, top)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "resnet101"])
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args()
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="hvd_prof_")
    capture(args.model, args.batch, args.steps, trace_dir,
            image_size=args.image_size)
    print(analyze(trace_dir, n_steps_hint=args.steps))


if __name__ == "__main__":
    main()
