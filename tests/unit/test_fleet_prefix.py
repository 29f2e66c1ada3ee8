"""Fleet-global prefix cache (inference/kv_hierarchy/prefix_directory
+ prefix-affinity routing + cross-replica plane adoption in fleet.py).

The contract under test (docs/INFERENCE.md, fleet-prefix section):
1. DIRECTORY — derived, lock-disciplined state: sync publishes a
   replica's live rows (version-gated), add fast-publishes an adopted
   row, invalidate drops a dead/recovered replica wholesale, match
   returns per-replica longest-match depths.
2. AFFINITY — the router folds matched-prefix depth into its score
   (score - AFFINITY_WEIGHT * depth / prefix_len); a replica holding a
   prompt's prefix wins the route at comparable load; dead replicas
   stay last whatever their affinity; the seeded tie-break sequence is
   unchanged from affinity-free ordering.
3. ADOPTION — a cold replica that wins on load ships the holder's
   prefix planes (export_prefix/adopt_prefix) instead of recomputing,
   and the adopted stream stays bit-identical to the sequential
   reference.
4. ACCEPTANCE (ISSUE) — on a template-heavy stream over a 3-replica
   CPU fleet, the affinity-on run's fleet prefix hit-rate is >= 2x the
   affinity-off run's, its prefilled tokens are strictly fewer, every
   stream (greedy AND sampled) is bit-identical to the single-engine
   oracle, and no replica compiles more than one program.
5. FAILOVER — killing the prefix-holding replica mid-stream
   invalidates its directory entries, replays its orphans
   bit-identically on survivors (zero lost), and the directory
   re-warms from survivor traffic.
"""

import types

import numpy as np
import pytest

from deepspeed_tpu.inference import (
    InferenceEngine,
    ServingFleet,
)
from deepspeed_tpu.inference.faults import Fault, FaultPlan
from deepspeed_tpu.inference.kv_hierarchy import PrefixDirectory
from deepspeed_tpu.inference.router import AFFINITY_WEIGHT, Router
from tests.unit.test_chunked_prefill import make_model
from tests.unit.test_telemetry import _parse_prom

_MODEL = {}


def _shared_model():
    if "m" not in _MODEL:
        _MODEL["m"] = make_model()
    return _MODEL["m"]


# Small-geometry serving config every fleet in this module shares: the
# prefix planes hold 16 positions, 4 rows, hits need >= 4 matched
# tokens. max_slots=2 keeps replicas easy to saturate so routing spills.
_SERVE = dict(max_slots=2, max_len=64, chunk_size=4, prefill_chunk=8,
              max_queue=32, prefix_cache=True,
              prefix_slots=4, prefix_len=16, min_prefix_len=4)


def _fleet(model, params, n_replicas=3, prefix_affinity=None, **cfg):
    merged = dict(_SERVE, **cfg)
    return ServingFleet(model, params, n_replicas=n_replicas,
                        config=merged, seed=0, start=False,
                        window_seconds=0.05,
                        prefix_affinity=prefix_affinity)


def _view(occ, q, slots=4, health="healthy"):
    return types.SimpleNamespace(slot_occupancy=occ, queue_depth=q,
                                 max_slots=slots, health=health)


# The template-heavy stream the acceptance tests share: 4 templates of
# 12 shared tokens (near-uniform use — a Zipf rank folded mod 4), short
# unique tails, greedy and sampled interleaved.
def _template_requests(cfg, n=24, n_templates=4, template_len=12,
                       seed=5, max_new=None):
    rng = np.random.RandomState(seed)
    templates = rng.randint(0, cfg.vocab_size,
                            size=(n_templates, template_len))
    reqs = []
    for i in range(n):
        tail = rng.randint(0, cfg.vocab_size, size=3 + (i % 4))
        prompt = np.concatenate([templates[i % n_templates], tail])
        kw = {"max_new_tokens": (3 + (i % 3) if max_new is None
                                 else max_new + (i % 3))}
        if i % 2:
            kw["temperature"] = 0.7
            kw["seed"] = 300 + i
        reqs.append((prompt.astype(np.int32), kw))
    return reqs


_REF_CACHE = {}


def _oracle(model, params, reqs):
    """Single-engine fault-free run of the template stream — what every
    fleet stream must match bit for bit (memoized per stream)."""
    key = tuple((tuple(int(t) for t in p), tuple(sorted(kw.items())))
                for p, kw in reqs)
    if key not in _REF_CACHE:
        eng = InferenceEngine(model, params, config=dict(_SERVE))
        handles = [eng.submit(p, **kw) for p, kw in reqs]
        eng.run()
        _REF_CACHE[key] = [list(h.tokens) for h in handles]
        eng.close()
    return _REF_CACHE[key]


# ----------------------------------------------------------- directory


def test_prefix_directory_sync_match_invalidate():
    d = PrefixDirectory()
    assert d.sync(0, [(1, 2, 3, 4), (9, 9)])
    assert not d.sync(0, [(9, 9), (1, 2, 3, 4)])  # set-equal: no churn
    assert d.sync(1, [(1, 2, 7)])
    assert len(d) == 3
    # Longest published match per replica; zero-depth replicas omitted.
    assert d.match([1, 2, 3, 4, 5]) == {0: 4, 1: 2}
    assert d.match([7, 7]) == {}
    # holders: full-span coverage only.
    assert d.holders([1, 2, 3, 4]) == [0]
    assert sorted(d.holders([1, 2])) == [0, 1]
    # add is the adoption fast-publish: idempotent, trie kept current.
    d.add(1, (1, 2, 3, 4))
    d.add(1, (1, 2, 3, 4))
    assert d.match([1, 2, 3, 4]) == {0: 4, 1: 4}
    snap = d.snapshot()
    assert snap["rows"] == {0: 2, 1: 2}
    # Death/recovery drops the replica wholesale.
    assert d.invalidate(0)
    assert not d.invalidate(0)
    assert d.match([1, 2, 3, 4]) == {1: 4}
    assert d.snapshot()["invalidations"] == 1
    # A re-sync from live store state re-admits it.
    d.sync(0, [(1, 2)])
    assert d.match([1, 2, 3]) == {0: 2, 1: 3}


def test_prefix_directory_entries_survive_partial_overlap():
    d = PrefixDirectory()
    d.sync(0, [(5, 6, 7, 8, 9)])
    # Diverging prompt still aliases the shared head (radix semantics).
    assert d.match([5, 6, 7, 1, 1]) == {0: 3}
    assert d.holders([5, 6, 7, 8, 9, 9]) == []


# ------------------------------------------------------------- routing


def test_router_affinity_blends_into_score():
    cold, warm = _view(0.5, 0), _view(0.75, 0)
    # Load alone prefers the colder replica...
    assert Router(seed=3).order([cold, warm]) == [cold, warm]
    # ...but a full-prefix match on the busier one outweighs the 0.25
    # load gap (AFFINITY_WEIGHT = 0.5 per full match).
    assert Router(seed=3).order([cold, warm],
                                affinity=[0.0, 1.0]) == [warm, cold]
    # An already-saturated holder loses anyway: occupancy 1 + queue
    # backlog beats the bounded affinity bonus.
    packed = _view(1.0, 4, slots=4)
    assert Router(seed=3).order([cold, packed],
                                affinity=[0.0, 1.0]) == [cold, packed]
    assert AFFINITY_WEIGHT == 0.5


def test_router_affinity_never_resurrects_dead_and_keeps_tiebreak():
    live, dead = _view(0.9, 3), _view(0.0, 0, health="dead")
    assert Router(seed=0).order([dead, live],
                                affinity=[1.0, 0.0]) == [live, dead]
    # Zero affinity must reproduce the affinity-free ordering draw for
    # draw: same seed, same views, same tie-break sequence.
    views = [_view(0.5, 1) for _ in range(4)]
    for v, name in zip(views, "abcd"):
        v.name = name
    plain = [[v.name for v in Router(seed=9).order(views)]
             for _ in range(3)]
    zeroed = [[v.name for v in Router(seed=9).order(
        views, affinity=[0.0] * 4)] for _ in range(3)]
    assert plain == zeroed


# ------------------------------------------------- adoption (fleet path)


def test_submit_sticks_to_prefix_holder_then_cold_replica_adopts():
    cfg, model, params = _shared_model()
    fleet = _fleet(model, params, n_replicas=2)
    try:
        rng = np.random.RandomState(2)
        head = rng.randint(0, cfg.vocab_size, size=12)

        def req(tail_seed):
            tail = np.random.RandomState(tail_seed).randint(
                0, cfg.vocab_size, size=4)
            return np.concatenate([head, tail]).astype(np.int32)

        fr0 = fleet.submit(req(0), max_new_tokens=3)
        while not fleet.idle:
            fleet.step()
        warm = fr0.replica_id
        # Affinity: follow-up requests at comparable load stick to the
        # replica that already holds the template.
        follow = []
        for s in range(1, 4):
            follow.append(fleet.submit(req(s), max_new_tokens=3))
            while not fleet.idle:
                fleet.step()
        assert all(fr.replica_id == warm for fr in follow)
        assert fleet.counters["affinity_routed"] >= 3
        assert fleet.counters["prefix_adoptions"] == 0
        # Saturate the holder (no stepping): load pushes a request onto
        # the cold replica, which must ADOPT the planes, not re-earn.
        burst = [fleet.submit(req(10 + s), max_new_tokens=3)
                 for s in range(6)]
        while not fleet.idle:
            fleet.step()
        owners = {fr.replica_id for fr in burst}
        assert owners == {0, 1}          # both replicas served
        assert fleet.counters["prefix_adoptions"] >= 1
        assert fleet.counters["prefix_bytes_shipped"] > 0
        # The adopted row is published: both replicas are now holders.
        snap = fleet.metrics()["fleet"]["prefix_directory"]
        assert set(snap["rows"]) == {0, 1}
        # Every stream, warm or adopted, aliased a real hit except the
        # very first.
        assert fleet.counters["prefix_misses"] == 1
    finally:
        fleet.close()


def test_export_adopt_validate_against_live_store():
    """export_prefix/adopt_prefix re-validate against the LIVE stores:
    a directory row that was evicted exports None; an acceptor that
    already covers the span refuses the copy."""
    cfg, model, params = _shared_model()
    fleet = _fleet(model, params, n_replicas=2)
    try:
        rng = np.random.RandomState(4)
        head = rng.randint(0, cfg.vocab_size, size=12)
        prompt = np.concatenate(
            [head, rng.randint(0, cfg.vocab_size, size=4)]
        ).astype(np.int32)
        fr = fleet.submit(prompt, max_new_tokens=3)
        while not fleet.idle:
            fleet.step()
        holder = fleet.replicas[fr.replica_id].engine
        other = fleet.replicas[1 - fr.replica_id].engine
        toks = [int(t) for t in prompt[:12]]
        exported = holder.export_prefix(toks)
        assert exported is not None
        matched, record = exported
        assert list(matched) == toks[:len(matched)]
        assert all(v.shape[2] == len(matched) for v in record.values())
        # Adopt once: planes land byte-identically in the new pool row.
        assert other.adopt_prefix(matched, record)
        row, depth = other._hier.store.lookup(list(matched))
        assert depth == len(matched)
        got = np.asarray(other._pool["pk"][:, row, :, :depth])
        assert np.array_equal(got, np.asarray(record["pk"]))
        # Second adopt is refused — the span is already covered.
        assert not other.adopt_prefix(matched, record)
        # Eviction invalidates the export path: wipe the holder's store
        # and the directory's stale row exports nothing.
        holder._hier.store.reset()
        assert holder.export_prefix(toks) is None
    finally:
        fleet.close()


# ----------------------------------------------------- ISSUE acceptance


def _run_template_stream(model, params, reqs, prefix_affinity, **cfg):
    fleet = _fleet(model, params, n_replicas=3,
                   prefix_affinity=prefix_affinity,
                   fault_injection=False, **cfg)
    try:
        handles = []
        for i, (prompt, kw) in enumerate(reqs):
            handles.append(fleet.submit(prompt, **kw))
            # A couple of steps per arrival: enough live load that
            # routing spreads across replicas, deterministic because
            # start=False steps inline.
            fleet.step()
            fleet.step()
        while not fleet.idle:
            fleet.step()
        tokens = [list(fr.tokens) for fr in handles]
        c = fleet.counters
        facts = {
            "tokens": tokens,
            "owners": [fr.replica_id for fr in handles],
            "hits": c["prefix_hits"],
            "misses": c["prefix_misses"],
            "hit_rate": fleet.prefix_hit_rate(),
            "prefill_tokens": c["prefill_tokens"],
            "adoptions": c["prefix_adoptions"],
            "affinity_routed": c["affinity_routed"],
            "compile_counts": dict(fleet.compile_counts),
        }
        assert all(fr.phase == "done" for fr in handles)
        return facts
    finally:
        fleet.close()


def test_template_heavy_acceptance_affinity_ab():
    """THE acceptance run: same template-heavy stream, 3-replica fleet,
    affinity on vs off. On-side: >= 2x the hit rate, strictly fewer
    prefilled tokens, and both sides bit-identical to the single-engine
    oracle (greedy AND sampled) with at most one compile per replica."""
    cfg, model, params = _shared_model()
    # 6 templates over 2 prefix rows per replica: the off side (load-
    # only routing spreads every template over every replica) thrashes
    # its LRU stores, the on side specializes each replica in the
    # templates it attracts.
    reqs = _template_requests(cfg, n=24, n_templates=6)
    ref = _oracle(model, params, reqs)

    on = _run_template_stream(model, params, reqs, prefix_affinity=True,
                              prefix_slots=2)
    off = _run_template_stream(model, params, reqs,
                               prefix_affinity=False, prefix_slots=2)

    # Bit-identity: routing policy may choose any replica; the streams
    # must not care (positional rng + numerics-neutral prefix planes).
    assert on["tokens"] == ref
    assert off["tokens"] == ref

    # The perf claim.
    assert on["hits"] + on["misses"] == off["hits"] + off["misses"]
    assert off["hit_rate"] < 0.3 and on["hit_rate"] > 0.5
    assert on["hit_rate"] >= 2.0 * off["hit_rate"]
    assert on["prefill_tokens"] < off["prefill_tokens"]
    assert on["affinity_routed"] > 0
    assert off["affinity_routed"] == 0 and off["adoptions"] == 0

    # ONE program per replica that served; nobody recompiles.
    for facts in (on, off):
        served = set(facts["owners"])
        for rid, count in facts["compile_counts"].items():
            assert count == (1 if rid in served else 0)


def test_prefix_holder_kill_invalidates_then_rewarms():
    """Kill the replica holding the hot template mid-stream: its
    directory entries invalidate with it, the orphans replay
    bit-identically on survivors (zero lost), and survivor traffic
    re-warms the directory."""
    cfg, model, params = _shared_model()
    # Budgets well past chunk_size (4): a 3-5 token answer can finish
    # inside ONE harvest and is never observably "mid-stream" — decode
    # must span several steps for the kill to land on live work.
    reqs = _template_requests(cfg, n=12, n_templates=1, max_new=10)
    ref = _oracle(model, params, reqs)
    fleet = _fleet(model, params, n_replicas=3, prefix_affinity=True,
                   fault_injection=True, recovery_max_retries=0)
    try:
        # Warm one template onto one replica.
        frs = [fleet.submit(reqs[0][0], **reqs[0][1])]
        while not fleet.idle:
            fleet.step()
        snap = fleet.metrics()["fleet"]["prefix_directory"]
        (holder,) = snap["rows"]
        assert holder == frs[0].replica_id
        # Pile the rest on; affinity concentrates them on the holder.
        frs += [fleet.submit(p, **kw) for p, kw in reqs[1:]]
        for _ in range(300):
            if any(fr.replica_id == holder and fr.tokens and not fr.done
                   for fr in frs):
                break
            fleet.step()
        else:
            pytest.fail("holder never reached mid-stream")
        fleet.inject_faults(
            FaultPlan(faults=(Fault("raise", step=0),)), replica=holder)
        assert fleet.wait_idle(timeout_s=120.0)

        assert all(fr.phase == "done" for fr in frs)       # zero lost
        assert [fr.tokens for fr in frs] == ref            # bit-identical
        assert not fleet.replicas[holder].alive
        assert fleet.failovers >= 1
        # The dead holder is gone from the directory...
        snap = fleet.metrics()["fleet"]["prefix_directory"]
        assert holder not in snap["rows"]
        assert snap["invalidations"] >= 1
        # ...and survivors re-earned the template while absorbing the
        # stream, so the directory is warm again.
        assert snap["rows"], "directory never re-warmed on survivors"
        assert all(rid != holder for rid in snap["rows"])
        match = fleet._directory.match(
            [int(t) for t in reqs[0][0]])
        assert match and all(d >= _SERVE["min_prefix_len"]
                             for d in match.values())
        # Rolling drain still honors SLO headroom with affinity on: the
        # dead replica is skipped, live ones drain and reopen.
        report = fleet.rolling_drain(timeout_s=30.0)
        by_rid = {r["replica"]: r for r in report}
        assert by_rid[holder] == {"replica": holder, "drained": False,
                                  "skipped": "dead"}
        live = [r for rid, r in by_rid.items() if rid != holder]
        assert all(r["drained"] or r.get("skipped") == "no_headroom"
                   for r in live)
        assert any(r["drained"] for r in live)
    finally:
        fleet.close()


# ----------------------------------------------------------- telemetry


def test_fleet_prometheus_exports_prefix_counters():
    """The new counters exist at 0 from engine construction (eager
    bank) and export per-replica through the merged registry."""
    cfg, model, params = _shared_model()
    fleet = _fleet(model, params, n_replicas=2)
    try:
        kinds, samples = _parse_prom(fleet.prometheus())
        for name in ("ds_tpu_prefix_adoptions_total",
                     "ds_tpu_prefix_bytes_shipped_total",
                     "ds_tpu_affinity_routed_total"):
            assert kinds[name] == "counter"
            rows = {k: v for k, v in samples.items() if k[0] == name}
            assert {dict(k[1])["replica"] for k in rows} == {"0", "1"}
            assert all(v == 0.0 for v in rows.values())
        # Serve one warm template + one affine follow-up, re-scrape:
        # affinity_routed moved on exactly the owning replica. The
        # fleet's aggregate windows against its own base, so a window
        # opened here reads these two requests whatever the fleet's
        # collector does to the engines' windows in between.
        fleet.metrics(reset=True)
        rng = np.random.RandomState(6)
        head = rng.randint(0, cfg.vocab_size, size=12)
        for s in range(2):
            tail = rng.randint(0, cfg.vocab_size, size=4)
            fleet.submit(np.concatenate([head, tail]).astype(np.int32),
                         max_new_tokens=3)
            while not fleet.idle:
                fleet.step()
        assert fleet.counters["affinity_routed"] >= 1
        kinds, samples = _parse_prom(fleet.prometheus())
        routed = {dict(k[1])["replica"]: v
                  for k, v in samples.items()
                  if k[0] == "ds_tpu_affinity_routed_total"}
        assert sum(routed.values()) == fleet.counters["affinity_routed"]
        assert sum(1 for v in routed.values() if v) == 1
        # fleet.metrics() carries the same count for the window.
        assert fleet.metrics()["fleet"]["affinity_routed"] == \
            fleet.counters["affinity_routed"]
    finally:
        fleet.close()
