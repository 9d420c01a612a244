"""SLO burn-rate engine + health-probe tests (ISSUE 18).

Unit tier: the burn-rate math on a private registry (fire on both
windows, resolve when the short window clears, availability specs
scoring a gauge fleet), reset semantics, the alert-name catalog.
Probe tier: the exposition server's /healthz + /readyz answer 200/503
from the probe callables, and a real Server's readiness flips on
journal-plane death and lease loss (the acceptance criterion).
"""

from __future__ import annotations

import asyncio

import pytest

from hyperqueue_tpu.utils.metrics import (
    MetricsRegistry,
    REGISTRY,
    probe,
    start_exposition_server,
)
from hyperqueue_tpu.utils.slo import (
    BurnRule,
    DEFAULT_RULES,
    DEFAULT_SPECS,
    SloEngine,
    SloSpec,
    alert_names,
    window_scale,
)

pytestmark = pytest.mark.metrics

_PAGE = (BurnRule("page", 14.4, 3600.0, 300.0),)


def _latency_engine(reg):
    spec = SloSpec(
        name="tick", description="95% of ticks under 250 ms",
        metric="hq_test_tick_seconds", objective=0.95, threshold=0.25,
    )
    return SloEngine(registry=reg, specs=(spec,), rules=_PAGE, scale=1.0)


# ------------------------------------------------------------- burn math
def test_latency_slo_fires_and_resolves():
    reg = MetricsRegistry()
    h = reg.histogram("hq_test_tick_seconds", "d", buckets=(0.25, 1.0))
    eng = _latency_engine(reg)

    for _ in range(10):
        h.observe(1.0)                      # all bad (over threshold)
    assert eng.evaluate(now=0.0) == []      # one sample: no delta yet
    for _ in range(10):
        h.observe(1.0)
    fired = eng.evaluate(now=10.0)
    assert len(fired) == 1
    alert = fired[0]
    assert alert["alert"] == "tick:page" and alert["state"] == "firing"
    # 100% bad / 5% budget = 20x burn on both windows
    assert alert["burn_rate"] == pytest.approx(20.0)
    assert alert["burn_short"] == pytest.approx(20.0)
    # steady state: no new transition while it keeps firing
    for _ in range(10):
        h.observe(1.0)
    assert eng.evaluate(now=20.0) == []
    assert eng.badge() == {"firing": 1, "worst": "page"}
    assert [a["alert"] for a in eng.paging_alerts()] == ["tick:page"]

    # exported judgement rides the module gauges (global registry)
    burn = REGISTRY.get("hq_slo_burn_rate")
    assert burn.labels("tick", "1h").value == pytest.approx(20.0)
    assert REGISTRY.get("hq_slo_alerts_firing").labels("page").value == 1.0

    # recovery: the SHORT window clears first and resolves the alert
    # (now=400 puts the short-window baseline past the bad era)
    for _ in range(50):
        h.observe(0.1)                      # good
    resolved = eng.evaluate(now=400.0)
    assert len(resolved) == 1
    assert resolved[0]["state"] == "resolved"
    assert resolved[0]["fired_for"] == pytest.approx(390.0)
    assert eng.badge() == {"firing": 0, "worst": None}
    assert REGISTRY.get("hq_slo_alerts_firing").labels("page").value == 0.0
    # both transitions retained for `hq alerts` history
    assert [t["state"] for t in eng.alerts()["recent"]] == [
        "firing", "resolved"
    ]


def test_availability_slo_scores_gauge_fleet():
    reg = MetricsRegistry()
    g = reg.gauge("hq_test_shard_up", "d", labels=("shard",))
    spec = SloSpec(
        name="avail", description="99.9% shards up",
        metric="hq_test_shard_up", kind="availability", objective=0.999,
    )
    eng = SloEngine(registry=reg, specs=(spec,), rules=_PAGE, scale=1.0)

    g.labels("0").set(1.0)
    g.labels("1").set(0.0)                  # one dead shard
    assert eng.evaluate(now=0.0) == []
    fired = eng.evaluate(now=10.0)
    assert len(fired) == 1 and fired[0]["slo"] == "avail"
    # half the fleet down vs a 0.1% budget: an enormous burn
    assert fired[0]["burn_rate"] > 100

    g.labels("1").set(1.0)                  # shard recovered
    resolved = eng.evaluate(now=400.0)
    assert len(resolved) == 1 and resolved[0]["state"] == "resolved"


def test_no_traffic_means_no_burn():
    reg = MetricsRegistry()
    reg.histogram("hq_test_tick_seconds", "d", buckets=(0.25, 1.0))
    eng = _latency_engine(reg)
    # metric registered but never observed: evaluate must no-op cleanly
    assert eng.evaluate(now=0.0) == []
    assert eng.evaluate(now=10.0) == []
    assert eng.alerts()["firing"] == []


def test_reset_clears_windows_and_alerts():
    reg = MetricsRegistry()
    h = reg.histogram("hq_test_tick_seconds", "d", buckets=(0.25, 1.0))
    eng = _latency_engine(reg)
    for _ in range(10):
        h.observe(1.0)
    eng.evaluate(now=0.0)
    for _ in range(10):
        h.observe(1.0)
    assert eng.evaluate(now=10.0)           # fired
    eng.reset()
    assert eng.alerts()["firing"] == []
    assert eng.alerts()["recent"] == []
    assert REGISTRY.get("hq_slo_alerts_firing").labels("page").value == 0.0
    # windows restart clean: the old bad era is gone, not inherited
    assert eng.evaluate(now=20.0) == []


def test_alert_name_catalog_is_cross_product():
    names = alert_names()
    assert len(names) == len(DEFAULT_SPECS) * len(DEFAULT_RULES)
    assert "tick-latency:page" in names
    assert "shard-availability:ticket" in names


def test_window_scale_env(monkeypatch):
    monkeypatch.delenv("HQ_SLO_WINDOW_SCALE", raising=False)
    assert window_scale() == 1.0
    monkeypatch.setenv("HQ_SLO_WINDOW_SCALE", "0.01")
    assert window_scale() == pytest.approx(0.01)
    eng = SloEngine(registry=MetricsRegistry())
    assert eng.scale == pytest.approx(0.01)
    monkeypatch.setenv("HQ_SLO_WINDOW_SCALE", "bogus")
    assert window_scale() == 1.0


# ----------------------------------------------------------- HTTP probes
def test_probe_paths_answer_200_and_503():
    state = {"ok": True}

    def readyz():
        return state["ok"], {"checks": {"x": "ok" if state["ok"] else "bad"}}

    def broken():
        raise RuntimeError("boom")

    async def main():
        server, port = await start_exposition_server(
            lambda: "x 1\n", 0, host="127.0.0.1",
            probes={"/readyz": readyz,
                    "/healthz": lambda: (True, {"role": "test"}),
                    "/broken": broken},
        )
        loop = asyncio.get_running_loop()

        def ask(path):
            return loop.run_in_executor(None, probe, "127.0.0.1", port, path)

        status, payload = await ask("/readyz")
        assert status == 200 and payload["ok"] is True
        state["ok"] = False
        status, payload = await ask("/readyz")
        assert status == 503
        assert payload == {"checks": {"x": "bad"}, "ok": False}
        status, payload = await ask("/healthz")
        assert status == 200 and payload["role"] == "test"
        # a probe that raises IS unready — never a 500 or a hang
        status, payload = await ask("/broken")
        assert status == 503 and payload["error"] == "probe raised"
        server.close()
        await server.wait_closed()

    asyncio.run(main())


# ------------------------------------------- server readiness (acceptance)
class _FakeThread:
    def __init__(self, alive):
        self._alive = alive

    def is_alive(self):
        return self._alive


class _FakeJPlane:
    def __init__(self, alive=True):
        self._thread = _FakeThread(alive)


class _FakeLease:
    def __init__(self, age):
        self._age = age

    def age_seconds(self):
        return self._age


def _server(tmp_path):
    from hyperqueue_tpu.server.bootstrap import Server

    return Server(server_dir=tmp_path / "srv", reattach_timeout=60.0)


def test_server_readyz_flips_on_journal_death_and_lease_loss(tmp_path):
    server = _server(tmp_path)
    ok, detail = server._probe_readyz()
    assert ok, detail                       # fresh server: ready

    # journal-plane thread death flips readiness (and liveness)
    server.jplane = _FakeJPlane(alive=False)
    ok, detail = server._probe_readyz()
    assert not ok and detail["checks"]["journal_plane"] == "dead"
    hok, hdetail = server._probe_healthz()
    assert not hok and hdetail["reason"] == "journal plane dead"
    server.jplane = _FakeJPlane(alive=True)
    ok, _ = server._probe_readyz()
    assert ok
    hok, hdetail = server._probe_healthz()
    assert hok and "uptime" in hdetail

    # lease loss: an expired (or fenced) lease means a successor may own
    # the shard — this process must fail readiness immediately
    server.lease_timeout = 15.0
    server.lease = _FakeLease(age=3.0)
    ok, detail = server._probe_readyz()
    assert ok and detail["checks"]["lease"] == "ok"
    server.lease = _FakeLease(age=99.0)
    ok, detail = server._probe_readyz()
    assert not ok and detail["checks"]["lease"] == "stale"
    server.lease = _FakeLease(age=3.0)
    server.fenced = True
    ok, detail = server._probe_readyz()
    assert not ok and detail["checks"]["lease"] == "fenced"
    server.fenced = False

    # a firing page alert marks the server not-ready for NEW work
    server.slo._firing[("tick-latency", "page")] = {
        "alert": "tick-latency:page", "severity": "page",
    }
    ok, detail = server._probe_readyz()
    assert not ok and "tick-latency:page" in detail["checks"]["slo"]
    server.slo._firing.clear()
    ok, _ = server._probe_readyz()
    assert ok


def test_server_alerts_rpc_pages_on_slow_ticks_and_resolves(tmp_path):
    """The server's own engine over the default catalog, read through the
    `hq alerts` RPC: ticks over the 250 ms objective in the series the
    tick itself feeds (`hq_tick_phase_seconds{phase="total"}`) fire
    `tick-latency` at page severity, readiness follows, and good ticks
    resolve it."""
    from hyperqueue_tpu.server.reactor import _TICK_PHASE_SECONDS

    server = _server(tmp_path)
    ticks = _TICK_PHASE_SECONDS.labels("total")

    def alerts():
        return asyncio.run(server._client_alerts({"op": "alerts"}))

    try:
        for _ in range(10):
            ticks.observe(0.4)
        server.slo.evaluate(now=0.0)        # the baseline sample
        assert alerts()["firing"] == []
        for _ in range(10):
            ticks.observe(0.4)
        server.slo.evaluate(now=10.0)
        out = alerts()
        assert out["op"] == "alerts"
        firing = {a["alert"]: a for a in out["firing"]}
        assert firing["tick-latency:page"]["state"] == "firing"
        assert firing["tick-latency:page"]["severity"] == "page"
        assert server._alert_badge()["worst"] == "page"
        ok, detail = server._probe_readyz()
        assert not ok and "tick-latency:page" in detail["checks"]["slo"]

        for _ in range(500):
            ticks.observe(0.01)
        server.slo.evaluate(now=400.0)      # past the 5 m short window
        out = alerts()
        assert not [a for a in out["firing"] if a["slo"] == "tick-latency"
                    and a["severity"] == "page"]
        assert [t["state"] for t in out["recent"]
                if t["alert"] == "tick-latency:page"] == [
            "firing", "resolved"
        ]
    finally:
        server.slo.reset()
