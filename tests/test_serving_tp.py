"""Tensor-parallel serving (ISSUE 10): token streams at tp in {2, 4}
must be BIT-IDENTICAL to tp_size=1 for greedy AND seeded sampling across
decode horizons, chunked prefill, and prefix caching (the full matrix
cells are `slow`; a fast core pins tp=2 for both model families — GPT
exercises the fused-QKV column interleave, the layout most likely to
silently break). Plus: GQA/divisibility validation, the sorted-device-id
mesh regression (any jax.devices() ordering produces the same mesh and
the same tokens), tp=2 snapshot -> tp=4 restore exactly-once, the
compile-count guard under shard_map (still one executable per bucket,
and tp_size=1 jit keys UNCHANGED from the pre-TP engine), a poisoned-
module raise-on-touch proof that tp_size=1 runs zero TP code, cluster
sub-mesh carving, corpse tp=2 -> survivor tp=1 migration, and the TP
observability surface (collective histogram, per-shard gauges, `@tp=N`
lifecycle tags through tools/trace_summary.py).
"""
import functools
import importlib.util
import os
import sys
import types

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, LlamaConfig, LlamaForCausalLM,
)
from paddle_tpu.serving import (
    FaultInjector, RequestJournal, ServingCluster, ServingEngine,
)

if len(jax.devices()) < 4:
    pytest.skip("tensor-parallel tests need >= 4 fake devices",
                allow_module_level=True)


@functools.lru_cache(maxsize=None)
def _llama():
    paddle.seed(1234)
    m = LlamaForCausalLM(LlamaConfig.tiny())   # 4 heads, 2 kv -> tp<=2
    m.eval()
    return m


@functools.lru_cache(maxsize=None)
def _llama4():
    """kv_heads=4 variant: supports tp=4 (tiny's kv=2 caps at tp=2)."""
    paddle.seed(1234)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4,
        intermediate_size=128, max_position_embeddings=64))
    m.eval()
    return m


@functools.lru_cache(maxsize=None)
def _gpt():
    paddle.seed(1234)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


_ENGINE_KW = dict(page_size=4, num_pages=64, max_batch_size=4,
                  max_seq_len=48, decode_horizon=4)

_PROMPTS = [[7, 3, 9, 1, 4], [2, 8, 6, 5, 1, 9, 3, 7, 2],
            [4, 4, 1, 8, 8, 2, 6, 3, 9, 5, 1, 7, 3]]

# two-page shared system prompt (page_size=4) for the prefix-cache cell
_SHARED = [6, 1, 6, 1, 8, 0, 3, 3]
_SHARED_PROMPTS = [_SHARED + [7, 3, 9], _SHARED + [2, 8, 6, 5, 1],
                   _SHARED + [4, 4, 1, 8, 8, 2, 6]]


def _sampling_kw(i, seeded):
    return (dict(temperature=0.8, top_k=5, seed=100 + i) if seeded
            else dict(seed=7))


def _staggered(model, prompts=_PROMPTS, seeded=False, max_new=6, **kw):
    """Staggered arrivals (two up front, the rest trickling in between
    steps) -> token lists in arrival order. The batch composition
    mid-run therefore mixes prefill and decode exactly like the
    single-device parity tests."""
    eng = ServingEngine(model, **{**_ENGINE_KW, **kw})
    rids = [eng.add_request(p, max_new_tokens=max_new,
                            **_sampling_kw(i, seeded))
            for i, p in enumerate(prompts[:2])]
    for _ in range(2):
        eng.step()
    for j, p in enumerate(prompts[2:], start=2):
        rids.append(eng.add_request(p, max_new_tokens=max_new,
                                    **_sampling_kw(j, seeded)))
        eng.step()
    outs = eng.run()
    return eng, [outs[r] for r in rids]


# --------------------------------------------------------- token parity

class TestTokenParity:
    @pytest.mark.parametrize("seeded", [False, True])
    def test_llama_tp2_matches_tp1(self, seeded):
        _, want = _staggered(_llama(), seeded=seeded)
        _, got = _staggered(_llama(), seeded=seeded, tp_size=2)
        assert got == want

    def test_gpt_tp2_matches_tp1(self):
        """GPT's fused qkv = Linear(h, 3h) is the column-interleave
        hazard: a naive contiguous shard would split the (3, heads, hd)
        factorization and produce garbage, not an error."""
        _, want = _staggered(_gpt(), seeded=True)
        _, got = _staggered(_gpt(), seeded=True, tp_size=2)
        assert got == want

    def test_prefix_cache_parity_tp2(self):
        """Shared-prefix admission must reuse pages identically at tp=2:
        page ids are shard-replicated, so the radix tree and the offset
        prefill behave byte-identically to the single-device engine."""
        _, want = _staggered(_llama(), prompts=_SHARED_PROMPTS,
                             enable_prefix_caching=True)
        eng, got = _staggered(_llama(), prompts=_SHARED_PROMPTS,
                              enable_prefix_caching=True, tp_size=2)
        assert got == want
        assert eng.prefix_cache.stats()["hit_tokens"] >= len(_SHARED)

    def test_ragged_chunked_parity_tp2(self):
        """Chunked prefill defaults to the ragged mixed-step executable;
        at tp=2 that one flat program runs under shard_map (replicated
        flat ids, sharded pools) and must stay bit-identical."""
        kw = dict(enable_chunked_prefill=True, prefill_chunk_tokens=8)
        _, want = _staggered(_llama(), seeded=True, **kw)
        eng, got = _staggered(_llama(), seeded=True, tp_size=2, **kw)
        assert got == want
        cc = eng.compile_counts()
        assert cc["ragged"] >= 1 and cc["prefill_chunked"] == 0

    @pytest.mark.slow
    @pytest.mark.parametrize("chunked", [False, True])
    @pytest.mark.parametrize("horizon", [1, 8])
    @pytest.mark.parametrize("seeded", [False, True])
    @pytest.mark.parametrize("tp", [2, 4])
    def test_matrix(self, tp, seeded, horizon, chunked):
        """THE acceptance matrix: tp in {2,4} x greedy/seeded x horizon
        {1,8} x chunked on/off under staggered arrivals, every cell
        bit-identical to the same-config tp_size=1 run."""
        kw = dict(decode_horizon=horizon)
        if chunked:
            kw.update(enable_chunked_prefill=True, prefill_chunk_tokens=8)
        _, want = _staggered(_llama4(), seeded=seeded, **kw)
        _, got = _staggered(_llama4(), seeded=seeded, tp_size=tp, **kw)
        assert got == want, (tp, seeded, horizon, chunked)


# ----------------------------------------------------------- validation

class TestValidation:
    def test_gqa_requires_kv_heads_divisible(self):
        with pytest.raises(ValueError, match="num_key_value_heads"):
            ServingEngine(_llama(), tp_size=4, **_ENGINE_KW)

    def test_heads_divisibility(self):
        with pytest.raises(ValueError, match="num_attention_heads"):
            ServingEngine(_llama4(), tp_size=3, **_ENGINE_KW)

    def test_needs_enough_devices(self):
        with pytest.raises(ValueError, match="devices"):
            ServingEngine(_llama(), tp_size=2,
                          devices=jax.devices()[:1], **_ENGINE_KW)

    def test_tp_size_must_be_positive(self):
        with pytest.raises(ValueError, match="tp_size"):
            ServingEngine(_llama(), tp_size=0, **_ENGINE_KW)


# ----------------------------------------- device ordering (satellite 2)

class TestDeviceOrdering:
    def test_shuffled_device_list_same_mesh_same_tokens(self):
        """Regression: mesh construction sorts by device id, so ANY
        ordering of the device list — a shuffled jax.devices() included
        — builds the identical mesh and emits identical tokens."""
        devs = list(jax.devices()[:4])
        shuffled = [devs[2], devs[0], devs[3], devs[1]]
        _, want = _staggered(_llama(), tp_size=2)
        eng, got = _staggered(_llama(), tp_size=2, devices=shuffled)
        assert got == want
        ids = [d.id for d in eng._tp.devices]
        assert ids == sorted(ids) == [d.id for d in devs[:2]]

    def test_cluster_carves_sorted_disjoint_submeshes(self):
        devs = list(jax.devices())
        cl = ServingCluster(_tp_factory(), num_replicas=2, tp_size=2,
                            devices=list(reversed(devs)))
        carved = [[d.id for d in r.supervisor.engine._tp.devices]
                  for r in cl.replicas]
        assert carved == [[devs[0].id, devs[1].id],
                          [devs[2].id, devs[3].id]]
        with pytest.raises(ValueError, match="devices"):
            ServingCluster(_tp_factory(), num_replicas=8, tp_size=2)

    def test_cluster_tp_requires_capable_factory(self):
        with pytest.raises(ValueError, match="tp_size"):
            ServingCluster(lambda: ServingEngine(_llama(), **_ENGINE_KW),
                           num_replicas=2, tp_size=2)


# ------------------------------------------- snapshot across tp degrees

class TestSnapshotCrossDegree:
    def test_tp2_snapshot_restores_on_tp4_exactly_once(self):
        """The journal's token record is device-independent, so a tp=2
        engine's snapshot restores onto a tp=4 mesh (and vice versa)
        and every request continues bit-identically, exactly-once."""
        _, want = _staggered(_llama4())
        eng = ServingEngine(_llama4(), journal=RequestJournal(),
                            tp_size=2, **_ENGINE_KW)
        rids = [eng.add_request(p, max_new_tokens=6, seed=7)
                for p in _PROMPTS]
        for _ in range(3):               # part-way: some tokens delivered
            eng.step()
        snap = eng.snapshot()
        assert snap.config["tp_size"] == 2
        eng2 = ServingEngine(_llama4(), journal=eng._journal,
                             tp_size=4, **_ENGINE_KW)
        eng2.restore(snap)
        out = eng2.run()
        assert [out[r] for r in rids] == want
        eng2.scheduler.check_consistency()
        eng._journal.check_consistency()


# --------------------------------------------------- bounded compilation

class TestCompileCounts:
    def test_one_executable_per_bucket_under_shard_map(self):
        """The compile-count guard holds at tp=2: the input avals are
        unchanged (page tables, ids, knobs are replicated as-is), so one
        prefill bucket + one decode horizon still means exactly one
        executable each, sampling fused."""
        eng, _ = _staggered(_llama(), tp_size=2,
                            prefill_buckets=(16, 48))
        counts = eng.compile_counts()
        assert counts["prefill"] == 1, counts
        assert counts["decode"] == 1, counts
        assert counts["total"] == 2, counts

    def test_tp1_jit_keys_unchanged_and_disjoint_from_tp(self):
        """tp_size=1 compiles THE SAME executables as before this PR:
        its model-level jit-cache keys keep the pre-TP ("family", shape)
        form, while TP engines suffix ("tp", degree, device_ids) — the
        two populations never collide, so replicas of different degrees
        sharing one model never exchange executables."""
        paddle.seed(1234)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        _staggered(model)
        base_keys = set(model._serving_jit_cache)
        assert base_keys and all(len(k) == 2 for k in base_keys)
        _staggered(model, tp_size=2)
        tp_keys = set(model._serving_jit_cache) - base_keys
        assert tp_keys
        for k in tp_keys:
            assert k[2:] == ("tp", 2, (0, 1)), k


# ------------------------------------------------- zero-touch when off

class TestZeroTouchAtTp1:
    def test_tp1_never_imports_tp_module(self, monkeypatch):
        """Poison paddle_tpu.serving.tp in sys.modules: a tp_size=1
        engine (and a tp_size=1 cluster) must run a full request without
        touching it, and a tp_size=2 engine must trip the poison —
        proving the knob is the ONLY gate."""
        poison = types.ModuleType("paddle_tpu.serving.tp")

        def _boom(name):
            raise AssertionError(
                f"tp module touched at tp_size=1: {name}")

        poison.__getattr__ = _boom
        monkeypatch.setitem(sys.modules, "paddle_tpu.serving.tp", poison)
        _, out = _staggered(_llama(), prompts=_PROMPTS[:1])
        assert len(out[0]) > len(_PROMPTS[0])
        cl = ServingCluster(_tp_factory(), num_replicas=2)
        assert cl.tp_size == 1
        with pytest.raises(AssertionError, match="tp module touched"):
            ServingEngine(_llama(), tp_size=2, **_ENGINE_KW)


# -------------------------------------------------------- observability

class TestObservability:
    def test_collective_histogram_and_per_shard_gauges(self):
        eng, _ = _staggered(_llama(), tp_size=2)
        reg = eng.metrics
        h = reg.get("serving_tp_collective_seconds",
                    labels={"overlap": "off"})
        assert h is not None and h.count >= 3
        assert h.sum > 0.0
        g0 = reg.get("serving_kv_pages_free", labels={"shard": "0"})
        g1 = reg.get("serving_kv_pages_free", labels={"shard": "1"})
        assert g0 is not None and g1 is not None
        # accounting is shard-replicated: both shards report the same
        # number at every sample point
        assert g0.value == g1.value > 0
        st = eng.stats()
        assert st["tp_size"] == 2
        assert st["tp"]["devices"] == sorted(st["tp"]["devices"])
        assert st["tp"]["kv_heads_per_shard"] == 1

    def test_lifecycle_spans_tagged_and_stats_untagged(self):
        eng, _ = _staggered(_llama(), tp_size=2, prompts=_PROMPTS[:1])
        lc = eng._obs.lifecycle
        assert lc.tag == "tp=2"
        rid = lc.request_ids()[-1]
        # retained stages stay plain — only EMITTED span names carry the
        # tag (trace_summary strips it back out)
        assert "finished" in lc.stages(rid)
        assert not any("@" in s for s in lc.stages(rid))

    def test_trace_summary_parses_tp_tag(self):
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "trace_summary.py")
        spec = importlib.util.spec_from_file_location("trace_summary_tp",
                                                      path)
        ts = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ts)
        evs = [dict(ph="X", pid=1, tid=1, ts=t * 1000.0, dur=100.0,
                    name=f"serving.request[5].{stage}@tp=2")
               for t, stage in enumerate(
                   ("enqueued", "prefill", "first_token", "finished"))]
        tl = ts.request_timelines(evs)
        assert list(tl) == [5]
        assert [s for s, _, _ in tl[5]] == [
            "enqueued", "prefill", "first_token", "finished"]
        tags = ts.request_tags(evs)
        assert tags == {5: "tp=2"}
        out = ts.format_requests(tl, tags=tags)
        assert out.splitlines()[0] == "tensor-parallel: tp=2"
        assert "request 5 @tp=2:" in out


# ------------------------------------------------------------- cluster

def _tp_factory(**overrides):
    kw = dict(_ENGINE_KW, **overrides)

    def make(replica=None, fault_injector=None, tp_size=1, devices=None):
        return ServingEngine(_llama(), fault_injector=fault_injector,
                             tp_size=tp_size, devices=devices, **kw)
    return make


class TestClusterMigration:
    def test_corpse_tp2_migrates_to_tp1_survivor(self):
        """Replica 0 runs at tp=2, replica 1 at tp=1 (a heterogeneous
        factory — the uniform tp_size= knob is sugar over exactly this).
        Killing the tp=2 replica migrates its requests onto the tp=1
        survivor via the journal's device-independent token record, and
        every stream finishes bit-identical to a fault-free tp=1 run."""
        _, want = _staggered(_llama())

        def make(replica=None, fault_injector=None):
            return ServingEngine(
                _llama(), fault_injector=fault_injector,
                tp_size=2 if replica == 0 else 1,
                devices=jax.devices()[:2] if replica == 0 else None,
                **_ENGINE_KW)

        inj = [FaultInjector().fail_at("device_lost", 2),
               FaultInjector()]
        cl = ServingCluster(make, num_replicas=2, fault_injectors=inj,
                            supervisor_kw=dict(max_restarts=0))
        assert cl.replicas[0].supervisor.engine.tp_size == 2
        rids = [cl.add_request(p, max_new_tokens=6, seed=7)
                for p in _PROMPTS]
        out = cl.run()
        assert cl.health().count("dead") == 1
        assert [out[r] for r in rids] == want
        assert cl.check_consistency()
