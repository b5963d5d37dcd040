"""``correct`` must come out false when the timed path is broken
underneath. Each case runs ``run.py --rehearse`` (which skips the look for
a chip and drives the rest of a run at a tiny size on CPU devices) in a
process of its own, with one fault planted in the PROGRAM:

  unchanged     the step returns its state as it got it
  half_batch    half of the batch is left out, the mean taken over the rest
  no_exchange   the exchange between chips is left out (cells on 4 chips)

and, with no fault, ``correct`` is true. (No token or answer is produced by
a training cell, so there is none to alter.) Run by hand:
``python3 -m pytest benchmark/tests -q`` — not part of tier-1.

As a script: ``python3 benchmark/tests/test_faults.py <cell> <fault>``.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _plant(fault: str) -> None:
    sys.path.insert(0, ROOT)
    import horovod_tpu as hvd
    from horovod_tpu.models import resnet, transformer

    if fault == "none":
        return
    if fault == "unchanged":
        real = hvd.spmd

        def spmd(fn, **kw):
            kw.pop("donate_argnums", None)
            step = real(fn, **kw)

            def call(state, opt_state, batch):
                return state, opt_state, step(state, opt_state, batch)[2]

            return call

        hvd.spmd = spmd
    elif fault == "half_batch":
        lm, rn = transformer.make_loss_fn, resnet.make_loss_fn

        def lm_loss(cfg, **kw):
            f = lm(cfg, **kw)
            return lambda p, toks: f(p, toks[:, :toks.shape[1] // 2])

        def rn_loss(model, **kw):
            f = rn(model, **kw)
            return lambda v, b: f(v, (b[0][:len(b[1]) // 2],
                                      b[1][:len(b[1]) // 2]))

        transformer.make_loss_fn, resnet.make_loss_fn = lm_loss, rn_loss
    elif fault == "no_exchange":
        hvd.DistributedOptimizer = lambda opt, **kw: opt
        hvd.allreduce_gradients = lambda grads, **kw: grads
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)["workloads"]


def _cases():
    for cell in _cells():
        yield cell["name"], "none"
        yield cell["name"], "unchanged"
        yield cell["name"], "half_batch"
        if cell["chips"] > 1:
            yield cell["name"], "no_exchange"


@pytest.mark.parametrize("cell,fault", list(_cases()))
def test_fault_is_seen(cell, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), cell, fault],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["metrics"] == {}  # a CPU made it: no device metric
    assert result["correct"] is (fault == "none"), result["compared"]


if __name__ == "__main__":
    _plant(sys.argv[2])
    sys.path.insert(0, BENCH)
    import run

    sys.exit(run.main(["--workload", sys.argv[1], "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearse"]))
