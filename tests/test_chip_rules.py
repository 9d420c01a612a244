"""The rules that keep a run meant for the chip from passing on the host
(PR 21), all checked here on the CPU backend: `--scheduler tpu` is the chip
or no server, `multichip` does not turn a failed backend into a host solve,
the compile cache can be placed from outside, and chip_smoke.py has no CPU
mode — though its phase functions can be rehearsed with the scheduler
passed in.
"""

import subprocess
import sys
from pathlib import Path

import pytest

from hyperqueue_tpu.models.multichip import MultichipModel
from hyperqueue_tpu.server.bootstrap import Server

REPO = Path(__file__).resolve().parent.parent


def test_scheduler_tpu_refuses_a_backend_that_is_not_a_tpu(tmp_path):
    with pytest.raises(RuntimeError, match=r"needs a TPU.*'cpu'"):
        Server(server_dir=tmp_path, scheduler="tpu")


def test_multichip_backend_init_failure_stops_the_server(
    tmp_path, monkeypatch
):
    import jax

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        Server(server_dir=tmp_path, scheduler="multichip")


def test_multichip_on_one_accelerator_forces_the_device_kernel(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [object()])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = MultichipModel()
    assert model.get_mesh() is False
    # not "sync-probe-pending", "cost-model", ...: no cost model at all
    assert model._backend_decision(shape_key=()) == ("device", "forced-jax")


def test_multichip_on_one_cpu_device_keeps_the_host_solve():
    model = MultichipModel(n_devices=1)
    assert model.get_mesh() is False
    assert model._backend_decision(shape_key=()) == ("host", "cpu-host")


@pytest.fixture
def jax_cache_config():
    """configure_compile_cache changes process-wide jax config: put back
    what it found, so later tests in this worker write no cache."""
    import jax

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {name: getattr(jax.config, name) for name in names}
    yield
    for name, value in before.items():
        jax.config.update(name, value)


@pytest.mark.parametrize("outside", [None, "/some/dir"],
                         ids=["unset", "set-from-outside"])
def test_compile_cache_placement(monkeypatch, jax_cache_config, outside):
    import jax

    from hyperqueue_tpu.utils import jaxdev

    if outside is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    # what jax itself read from the environment at import
    jax.config.update("jax_compilation_cache_dir", outside)
    got = jaxdev.configure_compile_cache()
    if outside is None:
        # one fixed directory inside the checkout, whatever the cwd,
        # the server dir or the temp dir are
        assert got == str(REPO / ".jax_cache")
    else:
        assert got == outside  # left alone
    assert jax.config.jax_compilation_cache_dir == got
    # the sub-second slicer and scatter programs are cached too
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_chip_smoke_has_no_cpu_mode(tmp_path):
    """JAX_PLATFORMS=cpu is in the environment (conftest): the first child
    that needs the chip refuses to start, and so does the script."""
    done = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
    )
    assert done.returncode != 0
    assert '"ok"' not in done.stdout
    assert "FAILED served: server start" in done.stderr
    assert "needs a TPU" in done.stderr


def test_served_phase_rehearsal_accounts_for_every_task(tmp_path):
    """The smoke's `served` phase at 2 workers x 200 tasks with the
    scheduler passed in: real processes over TCP, all three request
    classes, and what a host run must show of itself."""
    import chip_smoke  # conftest puts the repo root on sys.path

    rec = chip_smoke.served("auto", n_workers=2, n_tasks=200,
                            workdir=tmp_path, timeout=120.0)
    chip_smoke.check_accounting(rec, 200)
    assert rec["tasks"] == rec["finished"] == 200
    assert len(rec["jobs"]) == 3
    # a host run says so: no device block, every solve counted as host
    assert rec["device"] is None
    assert rec["solve_backend"].startswith("host-")
    assert rec["solves_by_backend"]
    assert all(b.startswith("host-") for b in rec["solves_by_backend"])
    assert rec["worker_loaded"] == []
    assert rec["server_exit"] == 0
    with pytest.raises(SystemExit):
        chip_smoke.check_served_on_chip(rec)


def test_fused_phase_rehearsal_places_the_gang_through_a_server_built_core():
    """The smoke's `fused` phase with the scheduler passed in: on four
    virtual devices `multichip` builds what the chip's `tpu` does, a device
    model and a core that runs the fused tick."""
    import chip_smoke

    rec = chip_smoke.fused("multichip", n_workers=64, n_tasks=400)
    assert len(rec["gang_workers"]) == 4
    assert {t["backend"] for t in rec["ticks"]} == {"device-sharded"}
    assert rec["single_node_tasks_running"] > 0
    assert "gangs/apply" in rec["ticks"][0]["gang_phases_ms"]
    # what `--chips 4` asks of this phase on the mesh: every device the
    # process sees, as the server builds it
    import jax

    chip_smoke.check_fused_on("device-sharded", len(jax.devices()), rec)
    with pytest.raises(SystemExit):
        chip_smoke.check_fused_on("device-jax", 1, rec)
