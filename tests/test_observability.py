"""Observability plane: metrics registry semantics, Prometheus text
exposition grammar (hand-rolled v0.0.4 parser below — also imported by
the CI endpoint-scrape step), the HTTP endpoint, per-request lifecycle
records, ``stats()`` snapshot immutability under a concurrent scrape,
the ``StepMetrics`` export schema, watchdog stall semantics, and the
end-to-end silent-hang path: a GENUINELY wedged ``engine.step()`` (test
hook blocks inside the step lock), detected by heartbeat deadline,
hard-killed and recovered through ``FTSupervisor`` with greedy-parity
output."""
import re
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

from repro.configs import get_config
from repro.core import (EngineHandle, LiveRLRunner, LLMProxy, RunnerConfig,
                        ServerlessPlatform)
from repro.core.scheduler import STEP_METRICS_SCHEMA, StepMetrics
from repro.ft import FTConfig, FTSupervisor
from repro.models import Model
from repro.obs import (MetricsRegistry, MetricsServer, Watchdog,
                       instrument_proxy, instrument_runner,
                       instrument_service, watch_engines)
from repro.obs.server import CONTENT_TYPE
from repro.rewards.rule_based import REWARD_FNS
from repro.rl.engine import GenRequest, InferenceEngine
from repro.rl.trainer import (default_optimizer, init_train_state,
                              make_grpo_train_step)
from repro.serve import JobState, RolloutJob, RolloutService

# ---------------------------------------------------------------------------
# Prometheus text exposition format v0.0.4 — strict grammar parser.
# No external dependency: this IS the golden-format check. The CI
# endpoint-scrape step imports ``parse_prometheus`` from here.
# ---------------------------------------------------------------------------
_NAME = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
_TYPES = ("counter", "gauge", "histogram", "summary", "untyped")
_ESC = {"\\": "\\", '"': '"', "n": "\n"}


def _parse_label_block(line, i):
    """Parse ``{name="value",...}`` starting at ``line[i] == '{'``;
    honors the \\\\, \\", \\n escapes (a literal ``}`` inside a quoted
    value must NOT close the block). Returns (labels, index past '}')."""
    assert line[i] == "{"
    i += 1
    labels = {}
    while line[i] != "}":
        j = line.index("=", i)
        name = line[i:j]
        assert _LABEL.match(name), f"bad label name {name!r}"
        assert name not in labels, f"duplicate label {name!r}"
        assert line[j + 1] == '"', f"unquoted label value after {name!r}"
        i = j + 2
        val = []
        while True:
            c = line[i]
            if c == "\\":
                nxt = line[i + 1]
                assert nxt in _ESC, f"bad escape \\{nxt!r}"
                val.append(_ESC[nxt])
                i += 2
            elif c == '"':
                i += 1
                break
            else:
                val.append(c)
                i += 1
        labels[name] = "".join(val)
        if line[i] == ",":
            i += 1
    return labels, i + 1


def _base_family(name, families):
    """A sample named ``x_bucket``/``x_sum``/``x_count`` belongs to the
    histogram family ``x`` when one is declared."""
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix):
            base = name[: -len(suffix)]
            if families.get(base, {}).get("type") == "histogram":
                return base
    return name


def parse_prometheus(text):
    """Strict parse of the exposition body; any grammar violation raises
    AssertionError. Returns ``{family: {"help", "type", "samples":
    [(sample_name, labels_dict, value)]}}`` and enforces: TYPE/HELP
    declared at most once and before the family's samples; metric and
    label names match the spec charset; label values escape ``\\``,
    ``\"``, newline; histogram buckets are cumulative-monotone with an
    ascending ``le`` sequence ending at ``+Inf`` whose value equals
    ``_count``."""
    assert text.endswith("\n"), "exposition must end with a newline"
    families = {}
    seen_samples = set()
    for line in text.split("\n")[:-1]:
        assert line == line.strip("\r"), "no CR line endings"
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            kind, rest = line[2:6], line[7:]
            name, _, payload = rest.partition(" ")
            assert _NAME.match(name), f"bad metric name {name!r}"
            fam = families.setdefault(name, {"help": None, "type": None,
                                             "samples": []})
            key = "help" if kind == "HELP" else "type"
            assert fam[key] is None, f"duplicate {kind} for {name}"
            assert name not in seen_samples, \
                f"{kind} for {name} after its samples"
            if kind == "TYPE":
                assert payload in _TYPES, f"bad type {payload!r}"
            fam[key] = payload
            continue
        if not line or line.startswith("#"):
            continue
        i = 0
        while i < len(line) and line[i] not in "{ ":
            i += 1
        name = line[:i]
        assert _NAME.match(name), f"bad sample name {name!r}"
        labels = {}
        if i < len(line) and line[i] == "{":
            labels, i = _parse_label_block(line, i)
        rest = line[i:].split()
        assert 1 <= len(rest) <= 2, f"bad sample line {line!r}"
        value = float(rest[0])       # raises on malformed values
        base = _base_family(name, families)
        assert base in families and families[base]["type"] is not None, \
            f"sample {name!r} before its TYPE line"
        seen_samples.add(base)
        families[base]["samples"].append((name, labels, value))
    for fname, fam in families.items():
        if fam["type"] != "histogram":
            continue
        series = {}
        for name, labels, value in fam["samples"]:
            rest = {k: v for k, v in labels.items() if k != "le"}
            key = tuple(sorted(rest.items()))
            s = series.setdefault(key, {"buckets": [], "sum": None,
                                        "count": None})
            if name == fname + "_bucket":
                assert "le" in labels, "bucket without le label"
                s["buckets"].append((float(labels["le"]), value))
            elif name == fname + "_sum":
                s["sum"] = value
            elif name == fname + "_count":
                s["count"] = value
        for key, s in series.items():
            bounds = [b for b, _ in s["buckets"]]
            counts = [c for _, c in s["buckets"]]
            assert bounds == sorted(bounds), f"{fname}{key}: le not sorted"
            assert bounds and bounds[-1] == float("inf"), \
                f"{fname}{key}: missing +Inf bucket"
            assert counts == sorted(counts), \
                f"{fname}{key}: buckets not cumulative-monotone"
            assert s["sum"] is not None and s["count"] is not None, \
                f"{fname}{key}: missing _sum/_count"
            assert counts[-1] == s["count"], \
                f"{fname}{key}: +Inf bucket != _count"
    return families


# ---------------------------------------------------------------------------
# registry + exposition grammar
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", ("role",))
    c.labels(role="decode").inc()
    c.labels(role="decode").inc(2)
    assert c.labels(role="decode").value == 3
    with pytest.raises(ValueError):
        c.labels(role="decode").inc(-1)
    c.labels(role="decode").set_total(10)
    c.labels(role="decode").set_total(4)          # clamps monotone
    assert c.labels(role="decode").value == 10
    g = reg.gauge("g", "help")
    g.child().set(5)
    g.child().dec(2)
    assert g.child().value == 3
    h = reg.histogram("h_seconds", "help", buckets=(0.1, 1.0))
    h.child().observe(0.05)
    h.child().observe(0.5)
    h.child().observe(99.0)
    cum, total, n = h.child().snapshot()
    assert cum == [1, 2, 3] and n == 3 and total == pytest.approx(99.55)
    assert h.child().percentile(0.5) == pytest.approx(1.0)


def test_registry_rejects_kind_and_label_mismatch():
    reg = MetricsRegistry()
    reg.counter("m_total", "help", ("a",))
    reg.counter("m_total", "help", ("a",))        # get-or-create: same ok
    with pytest.raises(ValueError):
        reg.gauge("m_total", "help", ("a",))
    with pytest.raises(ValueError):
        reg.counter("m_total", "help", ("b",))
    with pytest.raises(ValueError):
        reg.counter("m_total", "help", ("a",)).labels(wrong="x")
    with pytest.raises(ValueError):
        reg.counter("1bad", "help")


def test_exposition_passes_grammar_with_nasty_labels():
    reg = MetricsRegistry()
    c = reg.counter("repro_nasty_total", "escapes: \\ and \n inside",
                    ("path", "q"))
    c.labels(path='a"b\\c\nd', q="x}y{z,w=v").inc(2)
    c.labels(path="plain", q="").inc()
    h = reg.histogram("repro_lat_seconds", "latency", ("op",),
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.labels(op="scrape").observe(v)
    reg.gauge("repro_g", "a gauge").child().set(-1.5)
    text = reg.render()
    fams = parse_prometheus(text)
    assert fams["repro_nasty_total"]["type"] == "counter"
    samples = {tuple(sorted(lab.items())): v
               for _, lab, v in fams["repro_nasty_total"]["samples"]}
    # the escaped label value round-trips exactly
    assert samples[(("path", 'a"b\\c\nd'), ("q", "x}y{z,w=v"))] == 2
    hist = fams["repro_lat_seconds"]
    assert hist["type"] == "histogram"
    buckets = [(lab["le"], v) for n, lab, v in hist["samples"]
               if n.endswith("_bucket")]
    assert [v for _, v in buckets] == [1, 2, 3, 4]
    assert buckets[-1][0] == "+Inf"


def test_http_endpoint_serves_exposition_and_404s():
    reg = MetricsRegistry()
    reg.counter("repro_x_total", "x").child().inc(7)
    calls = []
    reg.register_collector(lambda: calls.append(1))
    with MetricsServer(reg) as srv:
        resp = urllib.request.urlopen(srv.url)
        body = resp.read().decode("utf-8")
        assert resp.headers["Content-Type"] == CONTENT_TYPE
        assert calls, "scrape did not run collectors"
        fams = parse_prometheus(body)
        assert fams["repro_x_total"]["samples"][0][2] == 7
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url.replace("/metrics", "/nope"))
        assert ei.value.code == 404


# ---------------------------------------------------------------------------
# StepMetrics schema
# ---------------------------------------------------------------------------
def test_step_metrics_to_dict_matches_schema():
    sm = StepMetrics(step=3, wall_s=1.5, loss=0.25, reward_mean=0.5,
                     evicted=1, aborted=2, trajs=4, fetch_s=0.2,
                     barrier_s=0.1, train_s=1.1, staleness=1,
                     push_wait_s=0.01, lock_wait_s=0.06, sync_s=0.03,
                     rollout_host_s=0.7, aborted_tokens=40,
                     evicted_tokens=12)
    d = sm.to_dict()
    assert list(d) == [name for name, _ in STEP_METRICS_SCHEMA]
    for name, typ in STEP_METRICS_SCHEMA:
        assert type(d[name]) is typ, f"{name}: {type(d[name])} != {typ}"
    assert d["step"] == 3 and d["train_s"] == 1.1 and d["staleness"] == 1
    assert (d["push_wait_s"], d["lock_wait_s"], d["sync_s"]) == \
        (0.01, 0.06, 0.03)
    assert d["rollout_host_s"] == 0.7
    assert (d["aborted_tokens"], d["evicted_tokens"]) == (40, 12)


# ---------------------------------------------------------------------------
# watchdog unit semantics (deterministic clock via check_once(now))
# ---------------------------------------------------------------------------
def test_watchdog_fires_once_per_episode_and_rearms():
    reg = MetricsRegistry()
    wd = Watchdog(deadline_s=0.5, registry=reg)
    beat, queued, stalls = [0], [True], []
    wd.register("eng", progress_fn=lambda: beat[0],
                queued_fn=lambda: queued[0],
                on_stall=lambda: stalls.append(1))
    assert wd.check_once(now=0.0) == []          # first poll arms
    beat[0] += 1
    assert wd.check_once(now=0.4) == []          # beat advanced: re-arm
    assert wd.check_once(now=0.8) == []          # 0.4s silent < deadline
    assert wd.check_once(now=1.0) == ["eng"]     # fired
    assert wd.check_once(now=5.0) == []          # once per episode
    beat[0] += 1
    assert wd.check_once(now=5.1) == []          # recovery beat re-arms
    assert wd.check_once(now=9.9) == ["eng"]     # new episode fires again
    assert stalls == [1, 1]
    text = reg.render()
    assert 'repro_watchdog_stalls_total{component="eng"} 2' in text


def test_watchdog_idle_component_never_fires():
    wd = Watchdog(deadline_s=0.1)
    wd.register("idle", progress_fn=lambda: 0, queued_fn=lambda: False,
                on_stall=lambda: pytest.fail("idle target fired"))
    for now in (0.0, 1.0, 2.0, 3.0):
        assert wd.check_once(now=now) == []


def test_watchdog_probe_exception_skips_poll():
    wd = Watchdog(deadline_s=0.1)
    fired = []
    wd.register("flaky", progress_fn=lambda: 1 / 0,
                queued_fn=lambda: True, on_stall=lambda: fired.append(1))
    assert wd.check_once(now=0.0) == []
    assert wd.check_once(now=9.0) == [] and not fired


# ---------------------------------------------------------------------------
# live-plane fixtures
# ---------------------------------------------------------------------------
def _fresh_state():
    cfg = get_config("tiny")
    model = Model(cfg, remat=False)
    return init_train_state(model, jax.random.PRNGKey(0),
                            default_optimizer(1e-3))


def _make_runner_factory(mode="sync", tasks=("game",), max_new=16,
                         max_len=320):
    def make(state):
        cfg = get_config("tiny")
        model = Model(cfg, remat=False)
        opt = default_optimizer(1e-3)
        eng = InferenceEngine(model, state.params, max_slots=8,
                              max_len=max_len, seed=3)
        proxy = LLMProxy([EngineHandle(eng, "local")])
        return LiveRLRunner(
            RunnerConfig(batch_size=4, group_size=2, alpha=2, mode=mode,
                         tasks=tasks, max_new_tokens=max_new,
                         temperature=0.0),
            proxy, state, jax.jit(make_grpo_train_step(model, opt)),
            ServerlessPlatform(), REWARD_FNS["format_bonus"],
            seq_len=max_len)
    return make


def _tap(runner):
    runner._stream = []
    orig = runner._pack
    runner._pack = lambda t: (runner._stream.append(
        [(tuple(x.tokens), round(float(x.reward), 6)) for x in t])
        or orig(t))


def _tiny_proxy(max_slots=4, max_len=128):
    cfg = get_config("tiny")
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, max_slots=max_slots,
                          max_len=max_len, seed=0)
    return LLMProxy([EngineHandle(eng, "local")])


# ---------------------------------------------------------------------------
# lifecycle records (data-plane SLO timestamps)
# ---------------------------------------------------------------------------
def test_lifecycle_records_stamp_request_timeline():
    proxy = _tiny_proxy()
    ttfts, gaps = [], []
    proxy.on_ttft = ttfts.append
    proxy.on_gap = gaps.append
    done = []
    proxy.submit(GenRequest(request_id="r0", prompt=[1, 5, 7],
                            max_new_tokens=8, temperature=0.0),
                 callback=done.append)
    live = proxy.lifecycle("r0")
    assert live is not None and live.t_first_token is None
    while proxy.busy:
        proxy.pump()
    assert len(done) == 1
    [lc] = proxy.drain_completed_lifecycles()
    assert proxy.drain_completed_lifecycles() == []    # drained
    assert lc.request_id == "r0"
    assert (lc.t_submit <= lc.t_admit <= lc.t_first_token <= lc.t_finish)
    assert lc.tokens == len(done[0].tokens)
    assert lc.ttft == pytest.approx(lc.t_first_token - lc.t_submit)
    assert ttfts == [pytest.approx(lc.ttft)]
    # per-token gaps cover every token after the first delivery
    assert len(lc.gaps()) >= 1 and len(gaps) == len(lc.gaps())
    assert all(g >= 0 for g in lc.gaps())
    # the drained record is a snapshot: mutating it can't touch the plane
    lc.token_times.clear()


# ---------------------------------------------------------------------------
# stats() snapshots stay immutable under a concurrent scrape
# ---------------------------------------------------------------------------
def test_scrape_during_traffic_returns_immutable_snapshots():
    proxy = _tiny_proxy(max_slots=4)
    reg = MetricsRegistry()
    instrument_proxy(reg, proxy)
    svc = RolloutService(proxy, max_inflight=8)
    svc.register_tenant("t", weight=1.0)
    instrument_service(reg, svc)
    scrape_errors, stop = [], threading.Event()

    def scraper():
        try:
            while not stop.is_set():
                text = reg.render()
                parse_prometheus(text)
                # mutate every snapshot surface we can reach — the live
                # plane must not notice
                st = proxy.stats()
                st["engines"].clear()
                st["routed_by_pool"]["fake"] = 99
                st["switch_log"].append({"bogus": 1})
                svc.stats().clear()
                proxy.handles[0].engine.stats().clear()
        except Exception as e:                    # noqa: BLE001
            scrape_errors.append(e)

    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    svc.start()
    try:
        tickets = [svc.submit("t", RolloutJob(
            kind="prompt", prompt=[1, 5, 7, 11 + i], max_new_tokens=8,
            temperature=1.0, stop_tokens=())) for i in range(12)]
        deadline = time.monotonic() + 60
        while any(not tk.done for tk in tickets):
            assert time.monotonic() < deadline, "traffic never drained"
            time.sleep(0.01)
    finally:
        stop.set()
        t.join(timeout=10)
        svc.close()
    assert not scrape_errors, scrape_errors
    assert svc.error is None
    st = proxy.stats()
    assert st["engines"], "scraper mutation leaked into live stats"
    assert "fake" not in st["routed_by_pool"]
    assert all("bogus" not in e for e in st["switch_log"])
    done = sum(1 for tk in tickets if tk.state == JobState.DONE)
    assert done == 12
    fams = parse_prometheus(reg.render())
    assert fams["repro_engine_decode_tokens_total"]["samples"][0][2] > 0
    assert fams["repro_slo_ttft_seconds"]["samples"], "no TTFT observed"
    # a serving-only plane takes no barrier, so its thread never parks
    assert fams["repro_service_handoffs_total"]["samples"][0][2] == 0
    assert svc.timings()["handoff_s"] == 0


# ---------------------------------------------------------------------------
# full-stack exporter: every StepMetrics schema field becomes a gauge
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_full_stack_scrape_exports_step_schema():
    runner = _make_runner_factory()(_fresh_state())
    reg = MetricsRegistry()
    instrument_runner(reg, runner)
    with runner:
        runner.run_steps(1)
        fams = parse_prometheus(reg.render())
    for name, _ in STEP_METRICS_SCHEMA:
        metric = f"repro_step_{name}"
        assert metric in fams, f"schema field {name} not exported"
        assert fams[metric]["samples"], f"{metric} has no sample"
    d = runner.history[-1].to_dict()
    got = {f"repro_step_{k}": v for k, v in d.items()}
    for metric, want in got.items():
        assert fams[metric]["samples"][0][2] == pytest.approx(want)
    # the rest of the stack exported too
    for fam in ("repro_engine_decode_tokens_total",
                "repro_buffer_consumed_total",
                "repro_serverless_invocations_total",
                "repro_service_completed_total",
                "repro_engine_device_wait_s_total",
                "repro_service_aborted_tokens_total",
                "repro_buffer_evicted_tokens_total",
                "repro_service_tick_seconds_total",
                "repro_service_tick_host_seconds_total",
                "repro_service_barrier_wait_seconds_total",
                "repro_service_handoffs_total",
                "repro_service_handoff_seconds_total"):
        assert fams[fam]["samples"], f"{fam} missing"
    # a sync-mode step ticks the service on the trainer thread, so no
    # service thread parks for its barrier
    assert fams["repro_service_tick_seconds_total"]["samples"][0][2] > 0
    assert fams["repro_service_handoffs_total"]["samples"][0][2] == 0
    assert fams["repro_service_handoff_seconds_total"]["samples"][0][2] == 0


# ---------------------------------------------------------------------------
# the PR-5 gap, closed end-to-end: a silently wedged engine step is
# detected by heartbeat deadline and recovered through FTSupervisor
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_watchdog_detects_wedged_engine_and_recovers_with_parity():
    make = _make_runner_factory()
    ref = make(_fresh_state())
    _tap(ref)
    with ref:
        ref.run_steps(2)

    runner = make(_fresh_state())
    _tap(runner)
    sup = FTSupervisor(runner, FTConfig(snapshot_every=1))
    eng = runner.proxy.handles[0].engine
    recover_errors = []
    try:
        runner.run_steps(1)
        # put real work in flight and cover it with a barrier snapshot
        runner._ensure_inflight()
        runner.proxy.pump()      # partial progress only: one K-step
        sup.last_snapshot = sup.snapshotter.capture(runner, 1)
        assert eng.has_pending
        # GENUINELY wedge the engine: the next step() blocks inside
        # _step_locked — holding _step_lock — until hard-killed. This is
        # a real hang, not a FailureInjector crash: without the watchdog
        # the pump thread below would block forever.
        eng._prestep_hook = lambda e: e._kill_evt.wait()
        recovered = threading.Event()

        def pump_loop():
            # sync mode has no service thread; tick like one would. The
            # first tick wedges inside engine.step() until the kill.
            while not recovered.is_set():
                runner.service.tick()

        pump_t = threading.Thread(target=pump_loop, daemon=True)
        pump_t.start()

        def recover(handle):
            try:
                sup.recover_hung_engine(handle)
            except Exception as e:                # noqa: BLE001
                recover_errors.append(e)
            finally:
                recovered.set()

        wd = Watchdog(deadline_s=0.4, poll_s=0.05)
        watch_engines(wd, runner.proxy, recover=recover)
        with wd:
            assert recovered.wait(timeout=60), "watchdog never recovered"
        pump_t.join(timeout=30)
        assert not pump_t.is_alive(), "pump thread still wedged"
        assert not recover_errors, recover_errors
        [ev] = sup.events
        assert ev.kind == "engine" and ev.recovered
        assert "watchdog" in ev.detail
        assert eng.crashes == 1, "hard kill did not reach the wedged step"
        # the reborn process carries neither the wedge nor the kill flag
        assert eng._prestep_hook is None and not eng._kill_evt.is_set()
        # stall bookkeeping: exactly one episode on the engine target
        [target] = wd._targets.values()
        assert target.stall_count == 1
        # the recovered plane trains on: greedy parity vs the unwedged
        # reference, and no traj_id trains twice
        runner.run_steps(1)
    finally:
        runner.close()
        sup.close()
    assert runner._stream == ref._stream
    ids = [i for b in runner.trained_log for i in b]
    assert len(ids) == len(set(ids))
