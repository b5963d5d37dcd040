"""chip_smoke.py off the chip: the same phase functions at toy width on the
CPU mesh, the gate's refusal, what a failed phase does to the exit code,
and the compile-cache helper. Whether the system starts on a TPU is
``python chip_smoke.py`` through the chip tool — not this file."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.utils import env as _env  # noqa: E402

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def toy_config() -> chip_smoke.SmokeConfig:
    model = transformer.TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, num_kv_heads=1,
        embed_dim=32, mlp_dim=64, max_seq_len=32, dtype=jnp.float32,
        attention="local")
    return chip_smoke.SmokeConfig(
        model=model, batch_per_chip=2, seq_len=32, train_steps=3,
        learning_rate=1e-2, pattern_period=8, profile_steps=2,
        serve_max_seq_len=16, serve_prompt_lens=(4, 4), serve_max_new=3,
        serve_max_batch=2, serve_block_size=4, speculate=2,
        attn_shape=(1, 64, 2, 1, 8), attn_window=16, bn_shape=(2, 2, 2, 8))


def _run(phases, capsys):
    try:
        failed = chip_smoke.run_phases(toy_config(), phases)
    finally:
        hvd.shutdown()
    out = capsys.readouterr().out
    assert failed == [], out
    for name, _ in phases:
        assert f"[{name}] ok in" in out
    return out


def test_phases_pass_at_toy_width(capsys):
    out = _run(tuple(p for p in chip_smoke.PHASES if p[0] != "kernels"),
               capsys)
    # 8 simulated devices: the subset groups formed and the step reduces.
    assert "group 1 (size 7)" in out
    assert "replicas bit-equal across 8 rank(s)" in out
    assert "2/2 requests equal generate" in out


@pytest.mark.slow  # nine interpret-mode Pallas compiles; CI unit-1 runs it
def test_kernels_phase_at_toy_size(capsys):
    out = _run((("kernels", chip_smoke.phase_kernels),), capsys)
    assert "channel_grad_sums" in out


def test_main_refuses_cpu_before_any_phase(monkeypatch, capsys):
    ran = []
    monkeypatch.setattr(chip_smoke, "PHASES",
                        (("probe", lambda cfg, ctx: ran.append(1)),))
    assert chip_smoke.main() == 2
    captured = capsys.readouterr()
    assert ran == []
    assert "platform='cpu'" in captured.err
    assert captured.out == ""  # no result line, nothing compiled


def _main_with(monkeypatch, phases):
    monkeypatch.setattr(chip_smoke, "gate", lambda: DEVICE)
    monkeypatch.setattr(chip_smoke, "describe_installation", lambda: None)
    monkeypatch.setattr(chip_smoke, "PHASES", phases)
    return chip_smoke.main()


def test_failed_phase_is_a_nonzero_exit(monkeypatch, capsys):
    ran = []

    def boom(cfg, ctx):
        raise RuntimeError("planted failure")

    rc = _main_with(monkeypatch, (
        ("boom", boom), ("after", lambda cfg, ctx: ran.append("after"))))
    captured = capsys.readouterr()
    assert rc == 1
    assert ran == ["after"]  # one call reports everything that is broken
    assert "planted failure" in captured.err
    assert "failed phases: boom" in captured.err
    assert '"ok"' not in captured.out


def test_all_phases_passing_prints_the_result_line(monkeypatch, capsys):
    rc = _main_with(monkeypatch, (("fine", lambda cfg, ctx: None),))
    assert rc == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": DEVICE}


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_a_set_directory_alone(monkeypatch,
                                                    restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert _env.use_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert _env.use_compile_cache() == want
    assert _env.use_compile_cache() == want  # fixed: no pid, no time
    assert jax.config.jax_compilation_cache_dir == want


def test_compile_cache_refuses_an_installed_package(monkeypatch, tmp_path,
                                                    restore_cache_dir):
    # An installed package's parent is site-packages, not a checkout:
    # nothing is written there, the caller is told to say where.
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(_env, "__file__", str(
        tmp_path / "site-packages" / "horovod_tpu" / "utils" / "env.py"))
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        _env.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
