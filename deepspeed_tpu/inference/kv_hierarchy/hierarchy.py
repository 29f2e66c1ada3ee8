"""The hierarchy facade: spec, config plumbing, and the engine's driver.

``HierarchySpec`` is the frozen shape contract ``kv_pool.init_pool``
consumes (which planes exist, their dtypes, the prefix store geometry).
``KVHierarchy`` is the host-side brain the engine calls at four points —
admission, prefill completion, release, recovery — plus the swap store
and the byte accounting behind the ``effective_slots`` gauge.

Accounting model (KV planes only; the toks ring and per-slot scalars are
identical across configurations and orders of magnitude smaller):

- ``flat_bytes_per_slot``: one fp plane pair, the pre-hierarchy baseline.
- ``bytes_per_slot``: the hierarchy slot — int8 codes plus fp32
  per-(head, position) scales when quantizing.
- ``prefix_store_bytes``: the resident shared planes, charged once.
- ``mean_aliased_bytes``: average bytes per admission a slot did NOT
  have to fill privately (cumulative aliased span / admissions).
- ``effective_slots(budget)``: how many concurrent sessions the budget
  carries — ``(budget - prefix_store) / (bytes_per_slot - mean_aliased)``
  with ``budget`` defaulting to the flat pool's footprint
  (``hbm_budget_bytes`` overrides for fixed-budget what-ifs).
"""

import dataclasses

import jax.numpy as jnp

from deepspeed_tpu.inference.kv_hierarchy.offload import HostSwapStore
from deepspeed_tpu.inference.kv_pool import slot_state_nbytes
from deepspeed_tpu.inference.kv_hierarchy.prefix_cache import PrefixStore


@dataclasses.dataclass(frozen=True)
class HierarchySpec:
    """Which tiers are on, and the prefix-store geometry. Frozen and
    hashable: it rides into ``init_pool`` and the pool shapes it implies
    are part of the traced-program contract."""

    int8: bool = False
    prefix: bool = False
    prefix_slots: int = 8
    prefix_len: int = 64
    min_prefix_len: int = 8
    offload: bool = False
    swap_slots: int = 8

    @property
    def enabled(self):
        return self.int8 or self.prefix or self.offload


def spec_from_config(config):
    """InferenceConfig -> HierarchySpec (field validation already done
    by InferenceConfig.__post_init__)."""
    return HierarchySpec(
        int8=bool(config.int8_kv),
        prefix=bool(config.prefix_cache),
        prefix_slots=int(config.prefix_slots),
        prefix_len=int(config.prefix_len),
        min_prefix_len=int(config.min_prefix_len),
        offload=bool(config.host_offload),
        swap_slots=int(config.swap_slots))


class _LocalCounters(dict):
    """Stand-in until the engine hands over its _CounterBank — same
    ``c[name] += n`` surface, plain ints underneath."""

    def __missing__(self, key):
        return 0


class KVHierarchy(object):
    """Host-side driver for the three tiers. All state here is derived
    and disposable — ``reset()`` after a pool rebuild restores the
    zero-knowledge starting point and replay re-earns everything."""

    def __init__(self, spec, gcfg, plane_len, max_slots,
                 hbm_budget_bytes=None, counters=None, pager=None):
        self.spec = spec
        self.plane_len = int(plane_len)
        self.max_slots = int(max_slots)
        self.hbm_budget_bytes = hbm_budget_bytes
        self.counters = _LocalCounters() if counters is None else counters
        # PAGED pool (inference/paging.py): the prefix tier stops owning
        # dedicated pk/pv planes and instead shares refcounted ARENA
        # PAGES into aliasing slots' block-table rows (full pages
        # outright, the straddle page copy-on-write). The allocator is
        # the one authority on page lifetime; the store's row payload
        # records which pages a row pins.
        self.pager = pager

        hd = gcfg.n_embd // gcfg.n_head
        self._fp_itemsize = jnp.dtype(
            getattr(gcfg, "dtype", jnp.float32)).itemsize
        kv_itemsize = 1 if spec.int8 else self._fp_itemsize
        # Bytes one cached position costs across all layers: k+v codes,
        # plus one fp32 scale each for k and v when quantizing.
        self._per_pos_bytes = gcfg.n_layer * gcfg.n_head * (
            hd * kv_itemsize * 2 + (8 if spec.int8 else 0))
        self._flat_per_pos_bytes = (gcfg.n_layer * gcfg.n_head
                                    * hd * self._fp_itemsize * 2)
        # A recurrent state a slot (``cache_spec().slot_state``): a fixed
        # size whatever the context, never quantised, swapped with the slot.
        self._slot_state_bytes = slot_state_nbytes(gcfg)

        self.store = PrefixStore(spec.prefix_slots) if spec.prefix else None
        if self.store is not None and pager is not None:
            self.store.on_evict = self._drop_prefix_pages
        self.swap_store = HostSwapStore(spec.swap_slots) if spec.offload \
            else None
        # Set by submit() when a QueueFull caller was told a swap would
        # free capacity; the next step's swap policy honors it even if
        # the queue has drained by then.
        self.swap_requested = False
        self._attach_len = {}      # rid -> aliased span (live attachments)
        self._pending_insert = {}  # rid -> span to publish at prefill end
        self._aliased_total = 0    # cumulative aliased bytes, all time

    # ------------------------------------------------------ engine hooks

    def _drop_prefix_pages(self, row, payload):
        """PrefixStore on_evict hook (paged mode): a row's contents were
        dropped — release its backing pages' store pin. Pages still
        shared into live slots keep those slots' own references."""
        pages, _span = payload
        self.pager.decref(pages)

    def _on_admit_paged(self, pool, req, slot):
        """Paged admission: a trie hit shares the stored row's FULL
        pages into the slot's block-table row outright (refcounted — no
        bytes move) and COPY-ON-WRITES the straddle page, so partial-
        prefix hits are safe: the slot's own prefill overwrites the
        straddle's positions past the certified span before the frontier
        reaches them. No ``prefix_len`` cap applies — dense mode caps
        the aliased span at the dedicated prefix plane's length, but
        here the shared bytes live in the same arena as everything else
        and any stored depth is shareable."""
        prompt = [int(t) for t in req.prompt]
        row, depth = self.store.lookup(prompt)
        payload = self.store.payload.get(row) if row is not None else None
        # The lane must still prefill >= 1 token to sample the first
        # output, so never alias the entire prompt.
        span = min(depth, len(prompt) - 1)
        if payload is not None:
            pages, stored_span = payload
            span = min(span, int(stored_span))
        if payload is None or span < self.spec.min_prefix_len:
            self.counters["prefix_misses"] += 1
            ins = len(prompt) - 1
            if ins >= self.spec.min_prefix_len:
                self._pending_insert[req.rid] = ins
            return pool
        pager = self.pager
        n_full = min(span // pager.page_len, len(pages))
        self.store.acquire(row, req.rid)
        self._attach_len[req.rid] = span
        self._aliased_total += span * self._per_pos_bytes
        self.counters["prefix_hits"] += 1
        pager.install_shared(slot, pages[:n_full])
        pool = dict(pool)
        if span > n_full * pager.page_len and n_full < len(pages):
            # Straddle page: private copy, eager arena-row copy of every
            # plane (codes AND scales). Positions past ``span`` inside it
            # are donor garbage the aliaser's own prefill overwrites.
            src = int(pages[n_full])
            dst = pager.cow_page(slot, src)
            for name in ("k", "v", "k_scale", "v_scale"):
                if name in pool:
                    pool[name] = pool[name].at[:, dst].set(
                        pool[name][:, src])
        req.cursor = span  # prefill starts past the aliased span
        if "toks" in pool:
            # The n-gram drafter reads the ring; the aliased span was
            # never prefilled by THIS slot, so write it by hand.
            pool["toks"] = pool["toks"].at[slot, :span].set(
                jnp.asarray(prompt[:span], jnp.int32))
        return pool

    def on_admit(self, pool, req, slot):
        """Admission hook: probe the trie, attach or record an insert
        intent, and stamp the slot's pid/pbase. Eager pool updates only
        — the traced programs see pid/pbase as ordinary donated inputs."""
        if self.store is None:
            return pool
        if self.pager is not None:
            return self._on_admit_paged(pool, req, slot)
        prompt = [int(t) for t in req.prompt]
        row, depth = self.store.lookup(prompt)
        # The lane must still prefill >= 1 token to sample the first
        # output, so never alias the entire prompt.
        span = min(depth, len(prompt) - 1, self.spec.prefix_len)
        pool = dict(pool)
        if row is not None and span >= self.spec.min_prefix_len:
            self.store.acquire(row, req.rid)
            self._attach_len[req.rid] = span
            self._aliased_total += span * self._per_pos_bytes
            self.counters["prefix_hits"] += 1
            req.cursor = span  # prefill starts past the aliased span
            pool["pid"] = pool["pid"].at[slot].set(row)
            pool["pbase"] = pool["pbase"].at[slot].set(span)
            if "toks" in pool:
                # The n-gram drafter reads the ring; the aliased span
                # was never prefilled by THIS slot, so write it by hand.
                pool["toks"] = pool["toks"].at[slot, :span].set(
                    jnp.asarray(prompt[:span], jnp.int32))
            return pool
        self.counters["prefix_misses"] += 1
        ins = min(len(prompt) - 1, self.spec.prefix_len)
        if ins >= self.spec.min_prefix_len:
            self._pending_insert[req.rid] = ins
        # Clear whatever attachment the slot's previous occupant left.
        pool["pid"] = pool["pid"].at[slot].set(-1)
        pool["pbase"] = pool["pbase"].at[slot].set(0)
        return pool

    def on_prefill_done(self, pool, req):
        """Publish a missed prefix: the slot's private plane now holds
        the prompt's k/v from position 0, so copy ``[:span]`` into a
        prefix row and index it in the trie."""
        span = self._pending_insert.pop(req.rid, None)
        if self.store is None or span is None:
            return pool
        before = self.store.evictions
        row = self.store.insert(tuple(int(t) for t in req.prompt[:span]))
        self.counters["prefix_evictions"] += self.store.evictions - before
        if row is None:  # every row pinned by live aliasers
            return pool
        slot = req.slot
        if self.pager is not None:
            # Paged publish: no copy at all — the slot's own pages
            # covering [:span] BECOME the stored row (incref is the
            # store's pin; they outlive the donor slot). Donor writes
            # >= span only touch the straddle page, which sharers COW.
            n = -(-span // self.pager.page_len)
            pages = self.pager.row_pages(slot)[:n]
            if len(pages) < n:
                return pool  # prefill never mapped that far (cancelled?)
            self.pager.incref(pages)
            self.store.payload[row] = (tuple(int(p) for p in pages),
                                       int(span))
            self.counters["prefix_inserts"] += 1
            return pool
        pool = dict(pool)
        for plane, prefix in (("k", "pk"), ("v", "pv"),
                              ("k_scale", "pk_scale"),
                              ("v_scale", "pv_scale")):
            if prefix in pool:
                pool[prefix] = pool[prefix].at[:, row, :, :span].set(
                    pool[plane][:, slot, :, :span])
        self.counters["prefix_inserts"] += 1
        return pool

    def on_handoff_in(self, req, pbase):
        """Acceptor-side handoff hook: the migrated record aliases a
        shared-prefix span of ``pbase`` positions, and the engine already
        verified (under the same lock) that THIS replica's trie holds a
        row covering it. Pin that row for the adopted request and record
        the attachment so byte accounting and release stay truthful.
        Returns the local row id the record's ``pid`` must be patched to.
        Deliberately does NOT count a hit or miss — the admission that
        earned those stats happened on the donor; re-counting here would
        double-book the fleet-wide hit rate."""
        row, depth = self.store.lookup([int(t) for t in req.prompt])
        assert row is not None and depth >= pbase, (row, depth, pbase)
        self.store.acquire(row, req.rid)
        self._attach_len[req.rid] = pbase
        return row

    def on_release(self, req):
        """Completion/cancel hook: drop the refcount pin, any pending
        insert, and any host swap record."""
        rid = req.rid
        if self.store is not None:
            self.store.release(rid)
            self._attach_len.pop(rid, None)
            self._pending_insert.pop(rid, None)
        if self.swap_store is not None:
            self.swap_store.pop(rid)

    def reset(self):
        """Crash recovery: the pool was just rebuilt, so every device
        plane this bookkeeping described is gone. Drop it all; replayed
        requests re-probe, re-insert and re-earn their hit rates.
        Counters are cumulative telemetry and keep counting."""
        if self.store is not None:
            self.store.reset()
        if self.swap_store is not None:
            self.swap_store.clear()
        self._attach_len.clear()
        self._pending_insert.clear()
        self.swap_requested = False

    def swap_capacity_left(self):
        return self.swap_store is not None and self.swap_store.capacity_left()

    # ------------------------------------------------- byte accounting

    def bytes_per_slot(self):
        return self._per_pos_bytes * self.plane_len + self._slot_state_bytes

    def flat_bytes_per_slot(self):
        return self._flat_per_pos_bytes * self.plane_len \
            + self._slot_state_bytes

    def prefix_store_bytes(self):
        if self.store is None:
            return 0
        if self.pager is not None:
            # Paged: no dedicated prefix planes — the store's cost is
            # exactly the arena pages its row payloads pin, live.
            pages = sum(len(p) for p, _ in self.store.payload.values())
            return pages * self.pager.page_len * self._per_pos_bytes
        return (self.spec.prefix_slots * self.spec.prefix_len
                * self._per_pos_bytes)

    def bytes_aliased_live(self):
        return sum(self._attach_len.values()) * self._per_pos_bytes

    def bytes_aliased_total(self):
        return self._aliased_total

    def hit_rate(self):
        hits = self.counters["prefix_hits"]
        total = hits + self.counters["prefix_misses"]
        return hits / total if total else 0.0

    def mean_aliased_bytes(self):
        total = (self.counters["prefix_hits"]
                 + self.counters["prefix_misses"])
        return self._aliased_total / total if total else 0.0

    def effective_slots(self, budget=None):
        if budget is None:
            budget = self.hbm_budget_bytes
        if budget is None:
            budget = self.flat_bytes_per_slot() * self.max_slots
        usable = budget - self.prefix_store_bytes()
        net = max(1.0, self.bytes_per_slot() - self.mean_aliased_bytes())
        return int(usable // net)
