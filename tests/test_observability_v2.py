"""Observability v2 (ISSUE 13): SLO goodput accounting, step-phase
breakdown, and the always-on flight recorder with crash post-mortems.

Unit layer (model-free): `HistogramWindow` percentiles pinned against
exact rank recomputation on synthetic streams (the same one-bucket
relative-error bound as `Histogram.percentile`), window isolation from
pre-anchor observations, exact `fraction_within` on point masses,
`SloTracker` goodput/attainment arithmetic, `FlightRecorder` ring
eviction + monotone sequence numbers, bundle build/dump round-trips.

Engine layer (tiny LLaMA, tests/test_serving.py's module-wide fixture
pattern): per-class goodput equals delivered tokens under generous
targets and zero under impossible ones, `stats()["slo"]` /
`stats()["step_breakdown"]` shapes, persistent-fault quarantine
auto-dumping a parseable bundle, and THE zero-cost guards — a
metrics-disabled or recorder-less engine executes no SLO/recorder code
at all (raise-on-touch, the PR 4/5/9 poisoned-object discipline).

Failure-forensics layer: `EngineSupervisor`'s EngineDead path leaves a
bundle whose timeline holds the fatal fault and the death; the cluster
acceptance criterion — a replica killed mid-run under migration — must
produce ONE bundle containing the fatal fault, the death/quarantine
AND the migration decisions, renderable by tools/postmortem.py; and
`ServingCluster.telemetry()` merges per-replica registries under
`replica=` labels with cluster-level Prometheus exposition.
"""
import functools
import importlib.util
import json
import math
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import (
    FlightRecorder, Histogram, HistogramWindow, MetricsRegistry,
    SloClass, SloTracker, build_postmortem, dump_postmortem,
)
from paddle_tpu.observability.flight_recorder import POSTMORTEM_SCHEMA
from paddle_tpu.serving import (
    EngineDead, FaultInjector, RequestJournal, ServingCluster,
    ServingEngine, describe_fault,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_postmortem_cli():
    mod = sys.modules.get("_postmortem_cli")
    if mod is not None:
        return mod
    spec = importlib.util.spec_from_file_location(
        "_postmortem_cli", os.path.join(REPO, "tools", "postmortem.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_postmortem_cli"] = mod
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _llama():
    paddle.seed(1234)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


_ENGINE_KW = dict(page_size=4, num_pages=64, max_batch_size=4,
                  max_seq_len=64, decode_horizon=4, retry_backoff_s=0.0)


def _engine(**kw):
    return ServingEngine(_llama(), **dict(_ENGINE_KW, **kw))


_PROMPTS = [[7, 3, 9, 1, 4], [2, 8, 6, 5, 1, 9, 3, 7, 2],
            [4, 4, 1, 8, 8, 2, 6, 3, 9, 5, 1, 7, 3]]

# generous targets every CPU-run observation meets / impossible ones
# nothing meets — the two ends that make goodput arithmetic exact
_EASY = SloClass("interactive", ttft_target_s=600.0, tpot_target_s=600.0)
_HARD = SloClass("tight", ttft_target_s=1e-12, tpot_target_s=1e-12)


# ----------------------------------------------------- histogram window

class TestHistogramWindow:
    def test_percentiles_match_exact_rank_recomputation(self):
        """THE estimator pin: on a synthetic stream the windowed
        percentile must land in the same log bucket as the exact
        rank-statistic of the post-anchor observations — a one-bucket
        (factor-of-growth) relative error bound, like
        Histogram.percentile."""
        rng = np.random.default_rng(7)
        h = Histogram("w_test_seconds")
        win = HistogramWindow(h)
        # pre-anchor noise the window must NOT see
        for v in rng.lognormal(mean=2.0, sigma=0.5, size=200):
            h.observe(float(v))
        win.anchor()
        post = [float(v) for v in
                rng.lognormal(mean=-4.0, sigma=1.0, size=500)]
        for v in post:
            h.observe(v)
        post.sort()
        n = len(post)
        assert win.count == n
        assert abs(win.sum - sum(post)) < 1e-9
        for q in (10.0, 50.0, 90.0, 95.0, 99.0):
            exact = post[max(1, math.ceil(q / 100.0 * n)) - 1]
            est = win.percentile(q)
            ratio = est / exact
            assert 1.0 / h.growth * 0.999 <= ratio <= h.growth * 1.001, \
                (q, est, exact)

    def test_window_excludes_pre_anchor_observations(self):
        h = Histogram("w_iso_seconds")
        win = HistogramWindow(h)
        for _ in range(50):
            h.observe(100.0)          # slow world before the anchor
        win.anchor()
        for _ in range(10):
            h.observe(0.001)          # fast world inside the window
        assert win.count == 10
        assert win.percentile(99.0) < 0.01    # the 100s are invisible
        assert h.percentile(50.0) > 1.0       # ...but still in the hist

    def test_fraction_within_exact_on_point_masses(self):
        h = Histogram("w_frac_seconds")
        win = HistogramWindow(h)
        win.anchor()
        for _ in range(5):
            h.observe(0.001)          # bucket entirely below the limit
        for _ in range(5):
            h.observe(100.0)          # bucket entirely above it
        assert win.fraction_within(1.0) == pytest.approx(0.5)
        assert win.fraction_within(500.0) == pytest.approx(1.0)
        assert win.fraction_within(1e-5) == pytest.approx(0.0)

    def test_empty_window_is_vacuously_attained(self):
        h = Histogram("w_empty_seconds")
        h.observe(3.0)
        win = HistogramWindow(h)
        win.anchor()                  # window opens AFTER the observation
        assert win.count == 0
        assert win.percentile(50.0) == 0.0
        assert win.fraction_within(1e-9) == 1.0
        assert win.summary() == Histogram.empty_summary()

    def test_re_anchor_slides_forward(self):
        h = Histogram("w_slide_seconds")
        win = HistogramWindow(h)
        win.anchor()
        h.observe(100.0)
        assert win.fraction_within(1.0) == pytest.approx(0.0)
        win.anchor()                  # slide: the 100 leaves the window
        h.observe(0.001)
        assert win.count == 1
        assert win.fraction_within(1.0) == pytest.approx(1.0)

    def test_percentile_range_validation(self):
        win = HistogramWindow(Histogram("w_val_seconds"))
        with pytest.raises(ValueError, match="percentile"):
            win.percentile(101.0)


# --------------------------------------------------------- SLO tracker

class TestSloClassValidation:
    def test_bad_targets_raise(self):
        with pytest.raises(ValueError, match="positive"):
            SloClass("x", ttft_target_s=0.0, tpot_target_s=1.0)
        with pytest.raises(ValueError, match="positive"):
            SloClass("x", ttft_target_s=1.0, tpot_target_s=-2.0)
        with pytest.raises(ValueError, match="name"):
            SloClass("", ttft_target_s=1.0, tpot_target_s=1.0)

    def test_tracker_validation(self):
        r = MetricsRegistry()
        with pytest.raises(ValueError, match="at least one"):
            SloTracker(r, [])
        with pytest.raises(ValueError, match="duplicate"):
            SloTracker(r, [_EASY, _EASY])
        with pytest.raises(ValueError, match="refresh_every"):
            SloTracker(r, [_EASY], refresh_every=0)


class TestSloTracker:
    def test_goodput_counts_only_within_target(self):
        r = MetricsRegistry()
        tr = SloTracker(r, [SloClass("a", 1.0, 0.1)])
        tr.first_token("a", 0.5)           # within 1.0 -> goodput
        tr.first_token("a", 2.0)           # violated -> observed only
        tr.decode_tokens("a", 0.05, 4)     # within 0.1 -> +4
        tr.decode_tokens("a", 0.5, 4)      # violated -> +0
        st = tr.summary()["a"]
        assert st["goodput_tokens"] == 5
        assert tr.goodput_tokens == 5
        assert st["lifetime"]["ttft"]["count"] == 2
        assert st["lifetime"]["tpot"]["count"] == 8

    def test_unknown_class_is_ignored(self):
        tr = SloTracker(MetricsRegistry(), [_EASY])
        tr.first_token(None, 0.1)
        tr.first_token("nope", 0.1)
        tr.decode_tokens("nope", 0.1, 3)
        assert tr.goodput_tokens == 0
        assert not tr.has_class("nope") and tr.has_class("interactive")

    def test_attainment_gauges_from_window_fractions(self):
        r = MetricsRegistry()
        tr = SloTracker(r, [SloClass("a", 1.0, 1.0)])
        for ttft in (0.001, 0.002, 0.003, 100.0):   # 3 of 4 within
            tr.first_token("a", ttft)
        tr.refresh(advance=False)
        st = tr.summary()["a"]
        assert st["attainment"]["ttft"] == pytest.approx(0.75)
        assert st["attainment"]["tpot"] == 1.0      # vacuous: no tpot obs
        g = r.get("serving_slo_attainment", {"slo_class": "a",
                                             "slo": "ttft"})
        assert g.value == pytest.approx(0.75)

    def test_step_tick_refreshes_and_advances_every_n(self):
        r = MetricsRegistry()
        tr = SloTracker(r, [SloClass("a", 1.0, 1.0)], refresh_every=2)
        tr.first_token("a", 100.0)          # violation in window
        tr.step_tick()                      # tick 1: no refresh yet
        g = r.get("serving_slo_attainment", {"slo_class": "a",
                                             "slo": "ttft"})
        assert g.value == 1.0               # still the init value
        tr.step_tick()                      # tick 2: refresh + advance
        assert g.value == pytest.approx(0.0)
        # the window advanced: a fresh violation-free window heals it
        tr.first_token("a", 0.001)
        tr.step_tick()
        tr.step_tick()
        assert g.value == pytest.approx(1.0)

    def test_summary_shape(self):
        tr = SloTracker(MetricsRegistry(), [_EASY, _HARD])
        s = tr.summary()
        assert set(s) == {"interactive", "tight"}
        for row in s.values():
            assert set(row) == {"targets", "window", "lifetime",
                                "attainment", "goodput_tokens"}
            assert set(row["window"]) == {"ttft", "tpot"}


# ----------------------------------------------------- flight recorder

class TestFlightRecorder:
    def test_capacity_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            FlightRecorder(capacity=0)

    def test_ring_evicts_oldest_seq_survives(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record("dispatch", i=i)
        assert len(rec) == 4
        assert rec.total_recorded == 10
        evs = rec.events()
        assert [e["seq"] for e in evs] == [7, 8, 9, 10]   # oldest-first
        assert [e["i"] for e in evs] == [6, 7, 8, 9]
        assert evs[0]["kind"] == "dispatch"
        ts = [e["t"] for e in evs]
        assert ts == sorted(ts)

    def test_clear(self):
        rec = FlightRecorder(capacity=8)
        rec.record("fault", site="dispatch")
        rec.clear()
        assert len(rec) == 0 and rec.total_recorded == 1

    def test_describe_fault_classes(self):
        from paddle_tpu.serving.resilience import InjectedFault
        d = describe_fault(InjectedFault("dispatch", 0, transient=True))
        assert d == {"exc": "InjectedFault", "transient": True,
                     "fatal": False}
        d = describe_fault(ValueError("boom"))
        assert d["exc"] == "ValueError" and not d["fatal"]


class TestPostmortemBundle:
    def test_build_without_sources_is_self_describing(self):
        b = build_postmortem("unit-test")
        assert b["schema"] == POSTMORTEM_SCHEMA
        assert b["reason"] == "unit-test"
        assert b["events"] == [] and b["events_total"] == 0
        assert b["metrics"] is None and b["requests"] == []
        json.dumps(b)               # JSON-able by construction

    def test_journal_tail_carries_counts_never_tokens(self):
        j = RequestJournal()
        j.submit(request_id=1, prompt=[1, 2, 3], max_new_tokens=4,
                 temperature=0.0, top_k=0, top_p=1.0, seed=7,
                 eos_token_id=None, deadline_wall=None)
        j.tokens(1, [5, 6, 7])
        b = build_postmortem("unit-test", journal=j)
        [row] = b["journal_tail"]
        assert row["delivered_tokens"] == 3
        text = json.dumps(b)
        # the delivered token VALUES must not appear anywhere
        assert "[5, 6, 7]" not in text and '"tokens": [5' not in text

    def test_dump_collision_safe_and_parseable(self, tmp_path):
        rec = FlightRecorder()
        rec.record("dead", reason="x")
        b = build_postmortem("dead: weird/reason !", recorder=rec)
        p1 = dump_postmortem(b, str(tmp_path))
        p2 = dump_postmortem(b, str(tmp_path))
        assert p1 != p2 and os.path.exists(p1) and os.path.exists(p2)
        assert "/" not in os.path.basename(p1).replace(".json", "") \
            .replace("postmortem-", "").replace("-", "")
        with open(p1) as f:
            again = json.load(f)
        assert again["schema"] == POSTMORTEM_SCHEMA
        assert again["events"][0]["kind"] == "dead"


# ----------------------------------------------------- engine SLO layer

class TestEngineSlo:
    def test_slo_classes_require_metrics(self):
        with pytest.raises(ValueError, match="enable_metrics"):
            _engine(slo_classes=[_EASY], enable_metrics=False)

    def test_unknown_class_rejected_at_add_request(self):
        eng = _engine(slo_classes=[_EASY])
        with pytest.raises(ValueError, match="SLO class"):
            eng.add_request([1, 2, 3], max_new_tokens=2,
                            slo_class="nope")
        # no SLO classes registered at all: any class name is unknown
        eng2 = _engine()
        with pytest.raises(ValueError, match="SLO class"):
            eng2.add_request([1, 2, 3], max_new_tokens=2,
                             slo_class="interactive")

    def test_goodput_equals_tokens_under_generous_targets(self):
        eng = _engine(slo_classes=[_EASY, _HARD])
        rid = eng.add_request(_PROMPTS[0], max_new_tokens=6,
                              temperature=0.0, slo_class="interactive")
        plain = eng.add_request(_PROMPTS[1], max_new_tokens=6,
                                temperature=0.0)   # classless: no goodput
        out = eng.run()
        assert len(out[rid]) == len(_PROMPTS[0]) + 6
        st = eng.stats()
        slo = st["slo"]["interactive"]
        # every one of the classed request's 6 tokens met the easy target
        assert slo["goodput_tokens"] == 6
        assert st["goodput_tokens"] == 6        # total == the one class
        assert slo["attainment"]["ttft"] == 1.0
        assert slo["attainment"]["tpot"] == 1.0
        assert slo["lifetime"]["ttft"]["count"] == 1
        assert slo["lifetime"]["tpot"]["count"] == 5
        # the classless request contributed nothing to any class
        assert st["slo"]["tight"]["goodput_tokens"] == 0
        rows = st["requests"]
        assert rows[rid]["slo_class"] == "interactive"
        assert rows[plain]["slo_class"] is None

    def test_impossible_targets_zero_goodput_zero_attainment(self):
        eng = _engine(slo_classes=[_HARD])
        eng.add_request(_PROMPTS[0], max_new_tokens=6, temperature=0.0,
                        slo_class="tight")
        eng.run()
        st = eng.stats()["slo"]["tight"]
        assert st["goodput_tokens"] == 0
        assert st["attainment"]["ttft"] == pytest.approx(0.0)
        assert st["attainment"]["tpot"] == pytest.approx(0.0)
        # raw throughput kept counting: goodput vs throughput IS the
        # overload signal
        assert eng.stats()["tokens_generated"] == 6

    def test_step_breakdown_shape_and_population(self):
        eng = _engine()
        eng.add_request(_PROMPTS[0], max_new_tokens=6, temperature=0.0)
        eng.run()
        bd = eng.stats()["step_breakdown"]
        assert set(bd) == {"schedule", "assemble", "dispatch", "drain",
                           "device_residency"}
        for phase in ("schedule", "assemble", "dispatch", "drain"):
            assert bd[phase]["count"] > 0, phase
            assert bd[phase]["sum"] >= 0.0
        assert bd["device_residency"]["count"] > 0
        # disabled metrics: same keys, all zero, no registry touched
        eng2 = _engine(enable_metrics=False)
        bd2 = eng2.stats()["step_breakdown"]
        assert set(bd2) == set(bd)
        assert all(v["count"] == 0 for v in bd2.values())

    def test_slo_refresh_every_validation(self):
        with pytest.raises(ValueError, match="refresh_every"):
            _engine(slo_classes=[_EASY], slo_refresh_every=0)


# ------------------------------------------------ engine recorder layer

class TestEngineRecorder:
    def test_recorder_sees_the_step_lifecycle(self):
        rec = FlightRecorder(capacity=1024)
        eng = _engine(flight_recorder=rec)
        rid = eng.add_request(_PROMPTS[0], max_new_tokens=6,
                              temperature=0.0)
        eng.run()
        kinds = [e["kind"] for e in rec.events()]
        for k in ("schedule", "dispatch", "drain", "terminal"):
            assert k in kinds, (k, kinds)
        term = [e for e in rec.events() if e["kind"] == "terminal"]
        assert term[-1]["rid"] == rid
        assert term[-1]["status"] == "finished"

    def test_quarantine_auto_dumps_bundle(self, tmp_path):
        fi = FaultInjector().fail_at("dispatch", 0, transient=False)
        rec = FlightRecorder(capacity=256)
        eng = _engine(fault_injector=fi, flight_recorder=rec,
                      postmortem_dir=str(tmp_path))
        rid = eng.add_request(_PROMPTS[0], max_new_tokens=6,
                              temperature=0.0)
        eng.run()
        assert eng.status(rid)[0] == "failed"
        assert eng.last_postmortem_path is not None
        with open(eng.last_postmortem_path) as f:
            bundle = json.load(f)
        assert bundle["schema"] == POSTMORTEM_SCHEMA
        assert bundle["reason"].startswith("quarantine-")
        kinds = [e["kind"] for e in bundle["events"]]
        assert "fault" in kinds and "quarantine" in kinds
        q = next(e for e in bundle["events"] if e["kind"] == "quarantine")
        assert rid in q["rids"]
        [row] = [r for r in bundle["requests"]
                 if r["request_id"] == rid]
        assert row["status"] == "failed"

    def test_dump_without_directory_raises(self):
        eng = _engine(flight_recorder=FlightRecorder())
        with pytest.raises(ValueError, match="directory"):
            eng.dump_postmortem("manual")

    def test_manual_bundle_from_healthy_engine(self, tmp_path):
        eng = _engine(flight_recorder=FlightRecorder(),
                      journal=RequestJournal())
        eng.add_request(_PROMPTS[0], max_new_tokens=4, temperature=0.0)
        eng.run()
        path = eng.dump_postmortem("manual", directory=str(tmp_path))
        with open(path) as f:
            b = json.load(f)
        assert b["reason"] == "manual"
        assert b["journal_tail"][0]["delivered_tokens"] == 4
        assert b["metrics"] is not None


# ------------------------------------------------------ zero-cost guards

class TestZeroCostWhenDisabled:
    def _poison(self, monkeypatch):
        import paddle_tpu.observability.flight_recorder as fr
        import paddle_tpu.observability.slo as slo

        def boom(*a, **kw):
            raise AssertionError(
                "SLO/recorder work on a disabled hot path")

        for cls, meth in [(slo.SloTracker, "first_token"),
                          (slo.SloTracker, "decode_tokens"),
                          (slo.SloTracker, "step_tick"),
                          (slo.SloTracker, "refresh"),
                          (slo.HistogramWindow, "anchor"),
                          (slo.HistogramWindow, "fraction_within"),
                          (fr.FlightRecorder, "record")]:
            monkeypatch.setattr(cls, meth, boom)
        monkeypatch.setattr(fr, "build_postmortem", boom)

    def test_metrics_disabled_engine_never_touches_slo_or_recorder(
            self, monkeypatch):
        eng = _engine(enable_metrics=False)
        assert eng._slo is None and eng._recorder is None
        eng.add_request([9, 8, 7], max_new_tokens=3, temperature=0.0)
        eng.run()                              # warm before poisoning
        self._poison(monkeypatch)
        rid = eng.add_request(_PROMPTS[0], max_new_tokens=4,
                              temperature=0.0)
        out = eng.run()
        assert len(out[rid]) == len(_PROMPTS[0]) + 4
        st = eng.stats()
        assert st["slo"] == {} and st["goodput_tokens"] == 0

    def test_metrics_on_but_no_slo_no_recorder_is_also_clean(
            self, monkeypatch):
        """Metrics alone must not drag SLO/recorder code in: the ISSUE 13
        layers are separately opt-in."""
        eng = _engine()
        assert eng._slo is None and eng._recorder is None
        eng.add_request([9, 8, 7], max_new_tokens=3, temperature=0.0)
        eng.run()
        self._poison(monkeypatch)
        rid = eng.add_request(_PROMPTS[0], max_new_tokens=4,
                              temperature=0.0)
        out = eng.run()
        assert len(out[rid]) == len(_PROMPTS[0]) + 4
        # stats() is cold-path: un-poison would be needed for slo, but
        # with no tracker it returns the zeroed shape without touching
        # the poisoned classes
        st = eng.stats()
        assert st["slo"] == {} and st["goodput_tokens"] == 0


# ------------------------------------------------- supervisor forensics

class TestSupervisorDeathBundle:
    def test_engine_dead_leaves_a_bundle(self, tmp_path):
        rec = FlightRecorder(capacity=512)
        fi = FaultInjector().fail_at("device_lost", 1)

        def factory():
            return _engine(fault_injector=fi, flight_recorder=rec,
                           postmortem_dir=str(tmp_path))

        from paddle_tpu.serving import EngineSupervisor
        sup = EngineSupervisor(factory, journal=RequestJournal(),
                               max_restarts=0)
        sup.add_request(_PROMPTS[0], max_new_tokens=6, temperature=0.0)
        with pytest.raises(EngineDead):
            sup.run()
        assert sup.postmortem is not None
        assert sup.postmortem["reason"].startswith("dead-")
        kinds = [e["kind"] for e in sup.postmortem["events"]]
        assert "fault" in kinds and "dead" in kinds
        dead = next(e for e in sup.postmortem["events"]
                    if e["kind"] == "dead")
        assert dead["restarts"] == 0
        assert sup.postmortem_path and os.path.exists(sup.postmortem_path)
        with open(sup.postmortem_path) as f:
            assert json.load(f)["schema"] == POSTMORTEM_SCHEMA

    def test_restart_recorded_when_supervisor_recovers(self):
        rec = FlightRecorder(capacity=512)
        fi = FaultInjector().fail_at("device_lost", 1)

        def factory():
            return _engine(fault_injector=fi, flight_recorder=rec)

        from paddle_tpu.serving import EngineSupervisor
        sup = EngineSupervisor(factory, journal=RequestJournal())
        rid = sup.add_request(_PROMPTS[0], max_new_tokens=6,
                              temperature=0.0)
        out = sup.run()
        assert len(out[rid]) == len(_PROMPTS[0]) + 6
        restarts = [e for e in rec.events() if e["kind"] == "restart"]
        assert len(restarts) == 1
        assert restarts[0]["readmitted"] == 1


# ------------------------------------- cluster acceptance + telemetry

def _recorded_factory(recorders, postmortems=None, **overrides):
    """One FlightRecorder per replica index, shared across engine
    rebuilds (the journal discipline: the forensic trail must survive
    the restart that created it)."""
    kw = dict(_ENGINE_KW, **overrides)

    def make(replica=None, fault_injector=None):
        rec = recorders.setdefault(replica, FlightRecorder(capacity=1024))
        return ServingEngine(_llama(), fault_injector=fault_injector,
                             flight_recorder=rec, **kw)
    return make


class TestClusterPostmortem:
    def test_replica_death_bundle_holds_fault_death_and_migration(
            self, tmp_path):
        """THE ISSUE 13 acceptance criterion: kill one of three replicas
        mid-run; the cluster must leave ONE parseable bundle whose
        timeline contains the fatal fault, the death, AND the migration
        decisions — and tools/postmortem.py must render it."""
        recorders = {}
        inj = [FaultInjector(),
               FaultInjector().fail_at("device_lost", 2),
               FaultInjector()]
        cl = ServingCluster(_recorded_factory(recorders),
                            num_replicas=3, fault_injectors=inj,
                            supervisor_kw=dict(max_restarts=0),
                            postmortem_dir=str(tmp_path))
        rids = [cl.add_request(p, max_new_tokens=6, seed=7)
                for p in _PROMPTS]
        out = cl.run()
        assert cl.health().count("dead") == 1
        assert all(len(out[r]) == len(p) + 6
                   for r, p in zip(rids, _PROMPTS))
        assert len(cl.postmortem_paths) == 1
        [path] = cl.postmortem_paths
        with open(path) as f:
            bundle = json.load(f)
        assert bundle["schema"] == POSTMORTEM_SCHEMA
        kinds = [e["kind"] for e in bundle["events"]]
        assert "fault" in kinds           # the fatal device_lost
        assert "dead" in kinds            # the supervisor's verdict
        assert "migrate" in kinds         # the failover decisions
        fatal = [e for e in bundle["events"]
                 if e["kind"] == "fault" and e.get("fatal")]
        assert fatal and fatal[0]["site"] == "device_lost"
        moves = [e for e in bundle["events"] if e["kind"] == "migrate"]
        assert all(m["src"] == 1 for m in moves)
        assert {m["dst"] for m in moves} <= {0, 2}
        # events stay seq-ordered: fault happens before the migrations
        seqs = [e["seq"] for e in bundle["events"]]
        assert seqs == sorted(seqs)
        assert bundle["info"]["cluster"]["replica"] == 1
        assert bundle["info"]["cluster"]["migrated"] == len(moves)
        # the dead replica's handle points at the bundle
        assert cl.replicas[1].supervisor.postmortem_path == path
        assert cl.telemetry()["postmortems"] == [path]

        cli = _load_postmortem_cli()
        text = cli.render(cli.load_bundle(path))
        assert "post-mortem:" in text
        assert "!!" in text               # the fatal fault line
        assert ">>" in text               # the migration line
        assert "r1->r" in text

    def test_telemetry_merges_replica_registries(self):
        cl = ServingCluster(_recorded_factory({}), num_replicas=2)
        rids = [cl.add_request(p, max_new_tokens=4, seed=7)
                for p in _PROMPTS]
        cl.run()
        tele = cl.telemetry()
        assert [r["index"] for r in tele["replicas"]] == [0, 1]
        assert all(r["alive"] for r in tele["replicas"])
        assert tele["dead_replicas"] == 0
        rows = tele["metrics"]["metrics"]
        tokens = [d for d in rows
                  if d["name"] == "serving_tokens_generated_total"]
        replicas_seen = {d["labels"]["replica"] for d in tokens}
        assert replicas_seen == {"0", "1"}
        assert sum(d["value"] for d in tokens) == 4 * len(rids)
        # cluster-level gauges keep their own replica labels: the fold
        # must setdefault, never overwrite
        health = [d for d in rows
                  if d["name"] == "serving_cluster_replica_health"]
        assert {d["labels"]["replica"] for d in health} == {"0", "1"}
        # and the exposition text is valid enough to grep
        assert 'replica="0"' in tele["prometheus"]
        assert "serving_tokens_generated_total" in tele["prometheus"]
