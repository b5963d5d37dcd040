"""Scale-out coverage: the multi-chip dry run beyond the 8-device world.

The driver validates ``__graft_entry__.dryrun_multichip`` at 8 devices;
``test_dryrun_16_devices`` re-runs it at 16 (combined DP×TP×SP mesh
included — tp=2, sp=2, dp=4) so pod-slice-shaped meshes stay covered by CI,
not just by manual runs. 32 devices is validated the same way but left out
of CI for wall clock; run
``python -c 'import __graft_entry__ as g; g.dryrun_multichip(32)'``.

Where the dry run may run is decided from what the devices ARE, and the
fast tests below pin that decision: enough devices → in this process; too
few on the CPU backend → a child on a simulated CPU mesh; too few on an
accelerator → an error, never a pass on a mesh the host does not have.
"""

import os
import sys
from unittest import mock

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import __graft_entry__ as g  # noqa: E402


def test_dryrun_16_devices():
    # The real child on a real 16-device mesh: the only run of
    # ``_dryrun_impl`` under the environment ``dryrun_multichip`` builds.
    g.dryrun_multichip(16)


def _devices(platform, n):
    return [mock.Mock(platform=platform, device_kind=f"fake {platform}")
            for _ in range(n)]


def test_enough_devices_runs_in_process(monkeypatch):
    import jax

    ran = []
    monkeypatch.setattr(jax, "devices", lambda: _devices("tpu", 4))
    monkeypatch.setattr(g, "_dryrun_impl", ran.append)
    g.dryrun_multichip(4)
    assert ran == [4]


def test_too_few_chips_on_an_accelerator_raises(monkeypatch):
    import jax
    import subprocess

    monkeypatch.setattr(jax, "devices", lambda: _devices("tpu", 1))
    monkeypatch.setattr(g, "_dryrun_impl", lambda n: pytest.fail("ran"))
    monkeypatch.setattr(subprocess, "run",
                        lambda *a, **k: pytest.fail("spawned a child"))
    with pytest.raises(RuntimeError, match="need 8 devices, the tpu "
                                           "backend has 1"):
        g.dryrun_multichip(8)


def test_failing_device_discovery_propagates(monkeypatch):
    import jax

    def broken():
        raise RuntimeError("libtpu did not start")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="libtpu did not start"):
        g.dryrun_multichip(8)


def test_too_few_cpu_devices_reexecutes_on_a_simulated_mesh(monkeypatch):
    import subprocess

    calls = []

    def fake_run(cmd, env, **kw):
        calls.append((cmd, env))
        return mock.Mock(returncode=0)

    monkeypatch.setattr(subprocess, "run", fake_run)
    g.dryrun_multichip(16)  # the suite's world has 8 CPU devices
    (cmd, env), = calls
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["HOROVOD_CPU_DEVICES"] == "16"
    assert "_dryrun_impl(16)" in cmd[-1]
