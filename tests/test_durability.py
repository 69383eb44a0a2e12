"""Durable serving: write-ahead journal + warm snapshots (serve.durability).

Three layers, cheapest first. Journal semantics are model-free and run in
milliseconds: record framing, group-commit fsync tracking, torn-tail
truncation at *every* byte offset (the hypothesis churn test — any crash
point must recover to the exact fold of the records wholly before it, zero
duplicated, zero lost synced finishes). The checkpoint tests pin the
atomicity fix: the destination directory is fsync'd *after* the rename, and
``restore_raw`` round-trips dynamic-shaped snapshots. The model tests drive
real fleets through a full power loss and pin the tentpole invariant:
``run_durable`` finishes the trace with zero lost rids, zero duplicated
completions, and per-request token streams bitwise identical to the
fault-free run — warm (snapshot) restarts re-prefilling no more than cold
(journal-only) ones — plus the silent-corruption guard: a NaN-poisoned KV
page retires its lane with ``finish_reason="corrupted"`` instead of
streaming garbage.
"""
import os

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.dist import checkpoint
from repro.models import model, transformer
from repro.serve import durability
from repro.serve import engine as eng_mod
from repro.serve import router as rt_mod
from repro.serve import traces
from repro.serve.api import SamplingParams, ServeRequest
from repro.serve.faults import FaultInjector, FaultPlan, PowerLoss
from repro.serve.paging import PageAllocator

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def dense():
    cfg = configs.get_config("smollm-360m").smoke()
    return cfg, model.init_params(jax.random.PRNGKey(0), cfg)


def _ecfg(**kw):
    base = dict(num_slots=2, max_cache=96, page_size=16, prefill_chunk=8,
                policy="immune", num_classes=3, latency_budget=96.0,
                pin_pages=8, num_pages=2 * (96 // 16) + 1 + 8)
    base.update(kw)
    return eng_mod.EngineConfig(**base)


def _fleet_trace(cfg, **kw):
    base = dict(tenants=2, num_requests=18, prefix_len=48, suffix_lens=(4,),
                decode_lens=(6,), hot_frac=0.9, burst_every=4, burst_size=3,
                seed=0)
    base.update(kw)
    return traces.fleet_trace(cfg, **base)


def _req(rid, plen=5, deadline=None, **kw):
    base = dict(max_new_tokens=4, seed=rid)
    base.update(kw)
    return ServeRequest(rid=rid, tokens=np.arange(plen, dtype=np.int32),
                        params=SamplingParams(**base), rclass=rid % 2,
                        arrival=rid, deadline=deadline)


def _tokens_by_rid(router):
    return {r.rid: list(r.out_tokens) for r in router.completed}


# ---------------------------------------------------------------------------
# journal semantics (model-free)
# ---------------------------------------------------------------------------
class TestJournal:
    def test_round_trip(self, tmp_path):
        p = str(tmp_path / "wal")
        j = durability.RequestJournal(p)
        r = _req(3, deadline=1.5)
        j.log_submit(r)
        j.log_emit(3, 11)
        j.log_emit(3, 12)
        j.log_finish(3, "stop", 9)
        j.close()
        j2 = durability.RequestJournal(p)
        assert set(j2.state) == {3}
        s = j2.state[3]
        assert s["tokens"] == list(range(5)) and s["out"] == [11, 12]
        assert s["fin"] == "stop" and s["fin_tick"] == 9
        assert s["rclass"] == 1 and s["arrival"] == 3
        assert s["deadline"] == 1.5
        assert SamplingParams(**s["params"]) == r.params

    def test_group_commit_cadence(self, tmp_path):
        j = durability.RequestJournal(str(tmp_path / "wal"), sync_every=3)
        j.log_submit(_req(0))              # submits always fsync
        base = j.syncs
        j.log_emit(0, 1)
        assert j.commit(0) is True         # first commit establishes the epoch
        j.log_emit(0, 2)
        assert j.commit(1) is False        # within the window: buffered
        j.log_emit(0, 3)
        assert j.commit(2) is False
        j.log_emit(0, 4)
        assert j.commit(3) is True         # 3 ticks elapsed -> one fsync
        assert j.syncs == base + 2
        assert j.commit(4) is False        # nothing dirty: no-op

    def test_power_loss_drops_unsynced_only(self, tmp_path):
        p = str(tmp_path / "wal")
        j = durability.RequestJournal(p, sync_every=100)
        j.log_submit(_req(1))
        j.log_emit(1, 7)
        j.commit(0)                        # epoch-setting sync covers tok 7
        j.log_emit(1, 8)                   # buffered, never fsync'd
        j.log_finish(1, "stop", 5)
        j.simulate_power_loss()
        j2 = durability.RequestJournal(p)
        assert j2.state[1]["out"] == [7]   # 8 and the finish died in the cache
        assert j2.state[1]["fin"] is None
        with pytest.raises(ValueError):
            j.log_emit(1, 9)               # dead journal refuses writes

    def test_submit_fsync_survives_power_loss(self, tmp_path):
        p = str(tmp_path / "wal")
        j = durability.RequestJournal(p, sync_every=100)
        j.log_submit(_req(5))
        j.simulate_power_loss()            # no commit() ever ran
        assert 5 in durability.RequestJournal(p).state

    def test_torn_tail_truncated(self, tmp_path):
        p = str(tmp_path / "wal")
        j = durability.RequestJournal(p)
        j.log_submit(_req(2))
        j.close()
        size = os.path.getsize(p)
        with open(p, "ab") as f:           # torn header + garbage payload
            f.write(b"\xff\xff\x00\x00abcdef")
        j2 = durability.RequestJournal(p)
        assert j2.truncated_bytes == 10 and j2.records == 1
        assert os.path.getsize(p) == size  # file physically truncated
        # corrupt the *checksum* of a complete record: also a torn tail
        with open(p, "r+b") as f:
            f.seek(size - 1)
            last = f.read(1)
            f.seek(size - 1)
            f.write(bytes([last[0] ^ 0xFF]))
        j3 = durability.RequestJournal(p)
        assert j3.records == 0 and j3.state == {}

    @hypothesis.given(cut=st.integers(0, 600))
    @hypothesis.settings(deadline=None, max_examples=60)
    def test_churn_any_crash_point_recovers_consistent_prefix(
            self, cut, tmp_path):
        """Truncate the journal at an arbitrary byte (mid-record included):
        recovery must equal the fold of exactly the records wholly before the
        cut — a consistent prefix with zero duplicated and zero lost
        finished rids."""
        p = str(tmp_path / f"wal{cut}")
        if os.path.exists(p):        # repeated draws must not share a journal
            os.remove(p)
        j = durability.RequestJournal(p)
        ends, recs = [], []

        def put(kind, *a):
            getattr(j, kind)(*a)
            j.sync()
            ends.append(os.path.getsize(p))
            recs.append((kind, a))

        put("log_submit", _req(0))
        put("log_emit", 0, 10)
        put("log_submit", _req(1, plen=3))
        put("log_emit", 0, 11)
        put("log_emit", 1, 20)
        put("log_finish", 0, "stop", 4)
        put("log_emit", 1, 21)
        put("log_finish", 1, "length", 6)
        j.close()
        total = os.path.getsize(p)
        cut = min(cut, total)
        with open(p, "r+b") as f:
            f.truncate(cut)
        got = durability.RequestJournal(p).state
        # expected: fold of the records whose last byte is <= cut
        want: dict = {}
        for (kind, a), end in zip(recs, ends):
            if end > cut:
                break
            if kind == "log_submit":
                want[a[0].rid] = {"out": [], "fin": None}
            elif kind == "log_emit":
                want[a[0]]["out"].append(a[1])
            else:
                want[a[0]]["fin"] = a[1]
        assert set(got) == set(want)
        for rid, w in want.items():
            assert got[rid]["out"] == w["out"]       # no dup, no reorder
            assert got[rid]["fin"] == w["fin"]       # no lost synced finish


# ---------------------------------------------------------------------------
# checkpoint atomicity fix + raw restore
# ---------------------------------------------------------------------------
class TestCheckpointDurability:
    def test_dir_fsync_after_rename(self, tmp_path, monkeypatch):
        """The classic rename-without-dirsync gap: the parent directory must
        be fsync'd *after* the atomic rename lands, else a power loss can
        roll the directory entry back and lose a checkpoint already reported
        durable."""
        events = []
        real_rename, real_open = os.rename, os.open
        real_fsync = os.fsync
        dirs_opened = {}

        def spy_rename(src, dst):
            events.append(("rename", dst))
            return real_rename(src, dst)

        def spy_open(path, flags, *a, **kw):
            fd = real_open(path, flags, *a, **kw)
            if os.path.isdir(path):
                dirs_opened[fd] = path
            return fd

        def spy_fsync(fd):
            if fd in dirs_opened:
                events.append(("dirsync", dirs_opened[fd]))
            return real_fsync(fd)

        monkeypatch.setattr(os, "rename", spy_rename)
        monkeypatch.setattr(os, "open", spy_open)
        monkeypatch.setattr(os, "fsync", spy_fsync)
        d = str(tmp_path / "ck")
        checkpoint.save(d, [np.arange(4)], step=1)
        renames = [i for i, (k, _) in enumerate(events) if k == "rename"]
        dirsyncs = [i for i, (k, v) in enumerate(events)
                    if k == "dirsync" and v == d]
        assert renames and dirsyncs
        assert max(dirsyncs) > max(renames), \
            f"no destination-dir fsync after the final rename: {events}"

    def test_restore_raw_dynamic_shapes(self, tmp_path):
        d = str(tmp_path / "ck")
        leaves = [np.frombuffer(b'{"a":1}', np.uint8),
                  np.ones((2, 3), np.float32),
                  np.arange(5, dtype=np.int64)]
        checkpoint.save(d, leaves, step=7)
        got, step = checkpoint.restore_raw(d)
        assert step == 7 and len(got) == 3
        for a, b in zip(leaves, got):
            np.testing.assert_array_equal(a, b)
        assert checkpoint.restore_raw(str(tmp_path / "none")) == (None, 0)

    def test_snapshot_blob_round_trip(self, tmp_path):
        d = str(tmp_path / "snap")
        meta = {"tick": 9, "replicas": [{"forest": []}]}
        kv = [np.full((1, 4, 2, 3), 0.5, np.float32)]
        durability.save_snapshot(d, 9, meta, kv)
        got_meta, got_kv, step = durability.load_snapshot(d)
        assert step == 9 and got_meta == meta
        np.testing.assert_array_equal(got_kv[0], kv[0])
        assert durability.load_snapshot(str(tmp_path / "none")) \
            == (None, [], 0)


# ---------------------------------------------------------------------------
# pinned-forest export/import (model-free allocator round trip)
# ---------------------------------------------------------------------------
class TestPinnedForest:
    def _alloc(self):
        return PageAllocator(12, 4, 2, 6, share_prefix=True, pin_pages=6,
                             num_classes=2, require_reservation=False)

    def test_export_import_round_trip(self):
        a = self._alloc()
        toks = np.arange(8, dtype=np.int32)          # two full pages
        a.ensure(0, 2)
        a.register_prefix(0, toks, rclass=1)
        a.release(0)                                  # refcount 0 -> pinned
        assert a.pages_pinned == 2
        forest = a.export_pinned()
        assert [e["parent"] for e in forest] == [-1, 0]
        b = self._alloc()
        placed = b.import_pinned(forest)
        assert len(placed) == 2 and b.pages_pinned == 2
        assert b.pinned_chain_keys() == a.pinned_chain_keys()
        # match needs one token past the chain: the last prompt token is
        # always recomputed, so probe with a 9-token prompt over the 8-token
        # registered prefix
        full, partial = b.match_prefix(np.arange(9, dtype=np.int32))
        assert len(full) == 2 and partial is None

    def test_import_respects_pin_budget(self):
        a = self._alloc()
        a.ensure(0, 2)
        a.register_prefix(0, np.arange(8, dtype=np.int32), rclass=0)
        a.release(0)
        b = PageAllocator(12, 4, 2, 6, share_prefix=True, pin_pages=1)
        placed = b.import_pinned(a.export_pinned())
        assert len(placed) == 1 and b.pages_pinned == 1


# ---------------------------------------------------------------------------
# poweroff plan grammar + injector signal (model-free)
# ---------------------------------------------------------------------------
class TestPoweroffPlan:
    def test_parse_and_pairing(self):
        plan = FaultPlan.parse("poweroff@12 restart@16 crash@3:r0")
        kinds = [e.kind for e in plan]
        assert kinds == ["crash", "poweroff", "restart"]
        assert all(e.replica == -1 for e in plan if e.kind != "crash")
        with pytest.raises(ValueError):
            FaultPlan.parse("poweroff@5:r1")          # fleet-wide: no :rN
        with pytest.raises(ValueError):
            FaultPlan.parse("restart@9")              # restart without poweroff
        with pytest.raises(ValueError):
            FaultPlan.parse("poweroff@5 poweroff@9")  # double off, no restart
        FaultPlan.parse("poweroff@5 restart@7 poweroff@9")  # re-off is fine

    def test_injector_raises_power_loss(self):
        class _Rt:
            tick = 12
            engines = [object()]
        inj = FaultInjector(FaultPlan.parse("poweroff@12 restart@16"))
        with pytest.raises(PowerLoss) as ei:
            inj.begin_tick(_Rt())
        assert ei.value.tick == 12 and ei.value.restart_tick == 16
        assert inj.stats()["poweroffs"] == 1
        # past the poweroff tick (post-recovery): restart is a no-op marker
        _Rt.tick = 16
        inj2 = FaultInjector(FaultPlan.parse("poweroff@12 restart@16"))
        inj2.begin_tick(_Rt())


# ---------------------------------------------------------------------------
# full power-loss recovery (model)
# ---------------------------------------------------------------------------
class TestPowerLossRecovery:
    def _factory(self, params, cfg, plan_spec, replicas=2, policy="immune"):
        def make():
            inj = FaultInjector(FaultPlan.parse(plan_spec))
            fleet = [eng_mod.Engine(params, cfg, _ecfg())
                     for _ in range(replicas)]
            return rt_mod.Router(fleet, rt_mod.RouterConfig(policy=policy),
                                 injector=inj)
        return make

    def test_poweroff_recover_bitwise_and_exactly_once(self, dense, tmp_path):
        cfg, params = dense
        ref_rt = rt_mod.Router([eng_mod.Engine(params, cfg, _ecfg())
                                for _ in range(2)],
                               rt_mod.RouterConfig(policy="immune"))
        ref = ref_rt.run(_fleet_trace(cfg))
        ref_toks = _tokens_by_rid(ref_rt)
        off = max(2, ref["ticks"] // 2)
        spec = f"poweroff@{off} restart@{off + 4}"
        rt, stats = durability.run_durable(
            self._factory(params, cfg, spec), _fleet_trace(cfg),
            str(tmp_path / "wal"), snapshot_dir=str(tmp_path / "snap"),
            snapshot_every=2)
        assert stats["restarts"] == 1
        got = _tokens_by_rid(rt)
        # zero lost rids, zero duplicates, bitwise-identical streams
        assert got == ref_toks
        assert len(rt.completed) == len({r.rid for r in rt.completed})
        assert stats["completed"] == ref["completed"]
        d = stats["durability"]
        assert d["recovered_finished"] + d["recovered_open"] > 0
        assert d["journal"]["truncated_bytes"] == 0  # clean group commits
        # every demanded request is accounted
        assert stats["completed"] + stats["shed"] + stats["rejected"] \
            + stats["corrupted"] + stats["unserved"] + stats["failed"] \
            == len(_fleet_trace(cfg))

    def test_resubmission_after_finish_is_deduped(self, dense, tmp_path):
        cfg, params = dense
        trace = _fleet_trace(cfg, num_requests=6)
        rt, stats = durability.run_durable(
            self._factory(params, cfg, "poweroff@4 restart@6"), trace,
            str(tmp_path / "wal"))
        journal = durability.RequestJournal(str(tmp_path / "wal"))
        rt2 = rt_mod.Router([eng_mod.Engine(params, cfg, _ecfg())
                             for _ in range(2)],
                            rt_mod.RouterConfig(policy="immune"))
        rt2.recover(journal, None)
        done_before = len(rt2.completed)
        out = rt2.run(_fleet_trace(cfg, num_requests=6))  # full re-drive
        assert rt2.dedup_drops == done_before == 6
        assert out["completed"] == 6                      # still exactly once
        assert _tokens_by_rid(rt2) == _tokens_by_rid(rt)

    def test_warm_restart_prefills_no_more_than_cold(self, dense, tmp_path):
        cfg, params = dense
        ref_rt = rt_mod.Router([eng_mod.Engine(params, cfg, _ecfg())
                                for _ in range(2)],
                               rt_mod.RouterConfig(policy="immune"))
        ref = ref_rt.run(_fleet_trace(cfg))
        off = (2 * ref["ticks"]) // 3
        spec = f"poweroff@{off} restart@{off + 4}"

        def run(snap):
            d = tmp_path / ("warm" if snap else "cold")
            d.mkdir()
            rt, stats = durability.run_durable(
                self._factory(params, cfg, spec), _fleet_trace(cfg),
                str(d / "wal"),
                snapshot_dir=str(d / "snap") if snap else None,
                snapshot_every=2)
            return rt, stats, sum(e.prefill_tokens for e in rt.engines)

        warm_rt, warm, warm_pf = run(True)
        cold_rt, cold, cold_pf = run(False)
        assert _tokens_by_rid(warm_rt) == _tokens_by_rid(cold_rt) \
            == _tokens_by_rid(ref_rt)
        assert warm["durability"]["recovered_pinned_pages"] > 0
        assert cold["durability"]["recovered_pinned_pages"] == 0
        # the pinned forest came back with its K/V: the warm fleet re-prefills
        # strictly less than the cold one (the 0.5x bar is gated, with a
        # bench-sized workload, in benchmarks/serve_engine.py durability)
        assert warm_pf < cold_pf


# ---------------------------------------------------------------------------
# silent-corruption guard (model)
# ---------------------------------------------------------------------------
class TestCorruptionGuard:
    def test_nan_page_retires_lane_as_corrupted(self, dense):
        cfg, params = dense
        eng = eng_mod.Engine(params, cfg, _ecfg(prefix_sharing=False))
        reqs = [ServeRequest(rid=i,
                             tokens=np.random.default_rng(i).integers(
                                 0, cfg.vocab_size, size=8).astype(np.int32),
                             params=SamplingParams(max_new_tokens=8),
                             rclass=i % 2, arrival=0) for i in range(2)]
        for r in reqs:
            eng.submit(r)
        while not all(eng.active_host[:2]) and eng.tick < 50:
            eng.step()
        victim = 0
        page = eng.alloc.owned(victim)[0]

        def poison(kind, leaf):
            if kind in ("attn", "moe"):
                return {"k": leaf["k"].at[:, :, page].set(jnp.nan),
                        "v": leaf["v"]}
            return leaf

        eng.pool = {"layers": transformer.map_block_caches(
            cfg, poison, eng.pool["layers"]), "pos": eng.pool["pos"]}
        for _ in range(3):
            eng.step()
        assert len(eng.corrupted) == 1
        bad = eng.corrupted[0]
        assert bad.rid == reqs[victim].rid
        assert bad.finish_reason == "corrupted" and bad.finish_tick >= 0
        assert eng.slots[victim] is None              # lane freed
        # the healthy lane keeps decoding to completion with finite tokens
        for _ in range(60):
            if not any(r is not None for r in eng.slots) and not eng.queue:
                break
            eng.step()
        assert len(eng.completed) == 1
        stats = eng.stats()
        assert stats["corrupted"] == 1
        assert stats["completed"] + stats["corrupted"] == 2

    def test_stream_reports_corrupted(self, dense):
        cfg, params = dense
        eng = eng_mod.Engine(params, cfg, _ecfg(prefix_sharing=False))
        req = ServeRequest(rid=0, tokens=np.arange(8, dtype=np.int32),
                           params=SamplingParams(max_new_tokens=8), arrival=0)
        outs = []
        poisoned = False
        for out in eng.stream([req], max_ticks=80):
            outs.append(out)
            if not poisoned and eng.active_host[0]:
                page = eng.alloc.owned(0)[0]

                def poison(kind, leaf):
                    if kind in ("attn", "moe"):
                        return {"k": leaf["k"].at[:, :, page].set(jnp.nan),
                                "v": leaf["v"]}
                    return leaf

                eng.pool = {"layers": transformer.map_block_caches(
                    cfg, poison, eng.pool["layers"]), "pos": eng.pool["pos"]}
                poisoned = True
        finals = [o for o in outs if o.finished]
        assert len(finals) == 1 and finals[0].finish_reason == "corrupted"
