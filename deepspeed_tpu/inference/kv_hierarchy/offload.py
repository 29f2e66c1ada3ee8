"""Host offload: fixed-shape slot capture/restore + the host swap store.

A swap-out captures ONE slot's entire device footprint — KV plane slices,
int8 scale slices when present, the token ring row, a model's recurrent
state a slot (``slot_*`` keys, slot-major like the scalars: a fixed-size
slice whatever the context, ``kv_pool.py``), every per-slot
scalar (including the prefix attachment fields), with ``active`` captured
*before* the engine deactivates the slot so restore reactivates it — in a
single batched ``jax.device_get``. Every captured array has a shape fixed
by the pool config, independent of which slot or how far into its stream
the session is: the transfer buffers never change shape, so nothing here
can perturb the compiled programs (capture/restore are eager ops, which
the recompile detector does not watch).

Restore writes the record back with eager ``.at[...].set`` into whatever
slot the scheduler hands out — the slot index need not match the one
captured, because every positional fact (pos, toks ring, prefix base)
travels inside the record. The restored plane is bit-identical to the
captured one, so the resumed greedy stream continues exactly where it
paused.

The same fixed-shape transport carries PREFIX rows between fleet
replicas (cross-replica plane adoption): ``capture_prefix_row`` snapshots
one shared-prefix row's first ``span`` positions — int8 codes and their
scales ship AS STORED, never dequantized — and ``restore_prefix_row``
writes them into a row of another replica's pool, where aliasing reads
them exactly as if that replica had prefilled the prefix itself.
"""

import time

import jax
import jax.numpy as jnp

# Plane-like pool entries sliced along the slot axis (axis 1).
_PLANE_KEYS = ("k", "v", "k_scale", "v_scale")

# Prefix-plane pool entries sliced along the row axis (axis 1).
_PREFIX_PLANE_KEYS = ("pk", "pv", "pk_scale", "pv_scale")

# Swap-victim blend: one second since a session's last emitted token
# counts like this many tokens of remaining budget. An idle session
# (a stalled client, a long think-time gap) becomes the preferred
# victim well before the largest-budget active session does.
IDLE_WEIGHT_TOKENS_PER_S = 32.0


def capture_slot(pool, slot):
    """Snapshot slot ``slot`` to host memory; returns {name: np.ndarray}."""
    slot = int(slot)
    arrs = {}
    for name, arr in pool.items():
        if name in ("pk", "pv", "pk_scale", "pv_scale"):
            continue  # shared prefix planes stay resident
        if name.startswith("aux_"):
            continue  # adapter aux state is global, not per-slot
        if name in _PLANE_KEYS:
            arrs[name] = arr[:, slot]
        else:
            arrs[name] = arr[slot]
    return jax.device_get(arrs)


def capture_slots(pool, slots):
    """Snapshot SEVERAL slots to host memory in ONE batched transfer;
    returns one record per slot, each restore_slot-compatible.

    The disaggregated handoff path's transport: every request whose
    prompt finishes in the same engine step ships together, mirroring
    ``harvest_snapshot``'s one-transfer-per-chunk discipline — N
    migrations cost one device round-trip, not N. Slices use a gather
    along the slot axis so the device sees a single fancy-index read
    per pool entry; the per-slot split happens host-side after the one
    ``jax.device_get``."""
    idx = jnp.asarray([int(s) for s in slots], jnp.int32)
    arrs = {}
    for name, arr in pool.items():
        if name in _PREFIX_PLANE_KEYS:
            continue  # shared prefix planes stay resident
        if name.startswith("aux_"):
            continue  # adapter aux state is global, not per-slot
        if name in _PLANE_KEYS:
            arrs[name] = arr[:, idx]
        else:
            arrs[name] = arr[idx]
    host = jax.device_get(arrs)
    return [{name: (val[:, i] if name in _PLANE_KEYS else val[i])
             for name, val in host.items()}
            for i in range(len(slots))]


def restore_slot(pool, slot, record):
    """Write a captured record into slot ``slot``; returns the new pool."""
    slot = int(slot)
    pool = dict(pool)
    for name, val in record.items():
        val = jnp.asarray(val, pool[name].dtype)
        if name in _PLANE_KEYS:
            pool[name] = pool[name].at[:, slot].set(val)
        else:
            pool[name] = pool[name].at[slot].set(val)
    return pool


# ------------------------------------------------------- paged variants
#
# A PAGED pool (inference/kv_pool.py paged layout) keeps k/v as page
# arenas [L, P, H/g, page_len, g*D] (g heads a lane tile: kv_pool.py;
# int8 scales [L, P, H, page_len]): a slot's device footprint is not a
# contiguous plane slice but the set of physical pages its block-table
# row names, so capture/restore take the explicit page list from the
# PageAllocator. Records ship ONLY LIVE PAGES — a 100-token session in a
# 2048-position plane moves ~1 page per layer, not the whole plane — as
# [L, n_pages, ...] stacks with the arena's OWN trailing dims (pages are
# indexed on axis 1 and nothing here looks inside one, so a record restores
# into any pool of the same model) plus the same per-slot scalars as
# the dense record. ``block_tbl`` never ships: it is host-owned derived
# state the allocator rebuilds at restore (the record's page ORDER is
# the row's logical order, which is all restore needs).


def capture_slot_paged(pool, slot, pages):
    """Snapshot one paged slot — its ``pages`` (logical order) gathered
    from the arenas plus its scalars/ring row — in one device_get."""
    slot = int(slot)
    idx = jnp.asarray([int(p) for p in pages], jnp.int32)
    arrs = {}
    for name, arr in pool.items():
        if name == "block_tbl" or name.startswith("aux_"):
            continue
        if name in _PLANE_KEYS:
            arrs[name] = jnp.take(arr, idx, axis=1)
        else:
            arrs[name] = arr[slot]
    return jax.device_get(arrs)


def capture_slots_paged(pool, slots, page_lists):
    """Snapshot several paged slots in ONE batched transfer (the
    disaggregated-handoff transport — mirrors capture_slots). All
    slots' pages concatenate into one gather; the per-slot split
    happens host-side after the single device_get."""
    counts = [len(p) for p in page_lists]
    flat = [int(p) for lst in page_lists for p in lst]
    pidx = jnp.asarray(flat, jnp.int32)
    sidx = jnp.asarray([int(s) for s in slots], jnp.int32)
    arrs = {}
    for name, arr in pool.items():
        if name == "block_tbl" or name.startswith("aux_"):
            continue
        if name in _PLANE_KEYS:
            arrs[name] = jnp.take(arr, pidx, axis=1)
        else:
            arrs[name] = arr[sidx]
    host = jax.device_get(arrs)
    records = []
    off = 0
    for i, n in enumerate(counts):
        records.append({name: (val[:, off:off + n]
                               if name in _PLANE_KEYS else val[i])
                        for name, val in host.items()})
        off += n
    return records


def restore_slot_paged(pool, slot, record, pages):
    """Write a paged record back: plane stacks scatter into the FRESH
    physical ``pages`` (len == the record's page count; the caller's
    allocator already owns them and will point the slot's table row at
    them), scalars into ``slot``. The physical pages need not match the
    captured ones — like the dense restore, every positional fact
    travels in the record."""
    slot = int(slot)
    idx = jnp.asarray([int(p) for p in pages], jnp.int32)
    pool = dict(pool)
    for name, val in record.items():
        val = jnp.asarray(val, pool[name].dtype)
        if name in _PLANE_KEYS:
            pool[name] = pool[name].at[:, idx].set(val)
        else:
            pool[name] = pool[name].at[slot].set(val)
    return pool


def pick_swap_victim(candidates, now=None,
                     idle_weight=IDLE_WEIGHT_TOKENS_PER_S,
                     live_pages=None, page_len=0):
    """The decoding session that can best afford to wait: reclaim value
    BLENDED with last-touch age, not budget order alone.

    Dense pools reclaim a fixed-size slot whoever the victim is, so the
    reclaim term is the CONFIGURED residual budget (max_new_tokens -
    emitted): many decode steps left to amortize the swap. A PAGED pool
    reclaims exactly the victim's live pages — pass ``live_pages`` (rid
    -> pages held) and ``page_len`` and the reclaim term becomes pages *
    page_len, the TRUE token-capacity the eviction frees: a long-context
    session holding 40 pages outranks a fresh one holding 2 whatever
    their configured budgets say.

    Score = reclaim + idle_weight * seconds-since-last-token; highest
    score is the victim, oldest rid on exact ties. A stale last-touch
    means the session is not producing and parking it costs nobody
    latency. Requests without a ``last_touch`` stamp score age 0."""
    if not candidates:
        return None
    if now is None:
        now = time.time()

    def _key(r):
        if live_pages is not None:
            reclaim = live_pages.get(r.rid, 0) * page_len
        else:
            reclaim = r.max_new_tokens - len(r.tokens)
        touched = getattr(r, "last_touch", None)
        age = 0.0 if touched is None else max(0.0, now - touched)
        return (reclaim + idle_weight * age, -r.rid)

    return max(candidates, key=_key)


def capture_prefix_row(pool, row, span):
    """Snapshot prefix row ``row``'s first ``span`` positions to host
    memory in one batched transfer; returns {name: np.ndarray}.

    The record holds the prefix planes exactly as stored — int8 codes
    and their fp32 scales when the pool quantizes — so shipping a row
    to another replica never round-trips through dequantization."""
    row, span = int(row), int(span)
    arrs = {}
    for name in _PREFIX_PLANE_KEYS:
        if name in pool:
            arrs[name] = pool[name][:, row, :, :span]
    return jax.device_get(arrs)


def restore_prefix_row(pool, row, record):
    """Write a captured prefix record into row ``row``; returns the new
    pool. Eager ``.at[].set`` — unwatched by the recompile detector,
    zero compiles. The row need not match the one captured (the span
    travels in the record's shapes), and positions past the span keep
    whatever the row held — aliasing only ever reads ``[:pbase]``."""
    row = int(row)
    pool = dict(pool)
    for name, val in record.items():
        val = jnp.asarray(val, pool[name].dtype)
        span = val.shape[2]  # planes [L, H, span, D]; scales [L, H, span]
        pool[name] = pool[name].at[:, row, :, :span].set(val)
    return pool


def record_nbytes(record):
    """Host bytes one captured record occupies (the shipping cost the
    ``prefix_bytes_shipped`` counter accounts)."""
    return int(sum(v.nbytes for v in record.values()))


class HostSwapStore:
    """rid -> captured record, bounded by the configured swap slots."""

    def __init__(self, capacity):
        self.capacity = int(capacity)
        self.records = {}

    def capacity_left(self):
        return len(self.records) < self.capacity

    def put(self, rid, record):
        if not self.capacity_left():
            raise RuntimeError("host swap store full "
                               "({} records)".format(self.capacity))
        self.records[rid] = record

    def pop(self, rid):
        return self.records.pop(rid, None)

    def __len__(self):
        return len(self.records)

    def nbytes(self):
        return sum(v.nbytes for rec in self.records.values()
                   for v in rec.values())

    def clear(self):
        self.records.clear()
