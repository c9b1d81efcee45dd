"""Paged KV cache: fixed-size pages + per-slot block tables (round 15).

ROADMAP open item 2. The round-14 serving engine preallocates every decode
lane at the full KV-ring width — a 20-token answer in a wide slot strands
almost all of its KV HBM, and the worst-case request sets the slot count
(i.e. the throughput ceiling) for everyone. This module replaces the
per-slot ring with the layout real serving engines use (vLLM's
PagedAttention, PAPERS.md):

  - **Page pool**: one `[L, num_pages, H, P, D]` K buffer and one V buffer
    (P = `page_size` token positions per page). Page 0 is the reserved
    NULL page — never allocated, the sink for masked writes — so a block
    table full of zeros is always safe to dereference.
  - **Block tables**: per-slot `[N, pages_per_slot]` int32 rows of page
    ids. The decode step dereferences them with ONE gather per layer, on
    `(layer, page)` of the stacked pool (`gather_view`), into exactly the
    `[N, H, W, D]` per-row view the round-14 vector-cursor attention
    already consumes — the indirection is localized in
    `gpt._apply_attention_paged` and the attention math is the ring path's
    own (`gpt._attend_over_cache`), which is what keeps the
    token-for-token parity bar provable.
  - **The stack is updated where it lies**: `gpt.forward_cached` threads
    the whole `[L, num_pages, H, P, D]` pools through its layer loop and
    the three device-side ops below take the stack and a layer index — a
    layer's pool is never sliced out of the stack and the stack is never
    rebuilt. A write is one scatter whose operand is the stack, so a
    caller whose stack dies at the write (the decode quantum's loop
    carry) has it updated in place; a tick moves the 2 x L x N x H x D
    values it writes, not the pool (PERF.md section 6, PR 26: slicing and
    restacking were 35% of the serving cell's device time).
  - **Allocation at request granularity**: a request admitted with prompt
    length p and budget m holds `ceil(min(p + m, width) / P)` pages — its
    actual worst case — instead of a full-width slot. The HBM a short
    answer strands is at most one page, and the pool (not the widest
    request) sets the concurrency ceiling.
  - **Shared-prefix reuse**: prompt prefixes are hashed at page
    granularity into a chained registry (parent-page + chunk-tokens ->
    page). A new request walks the registry, points its block table at
    the matched read-only pages with refcounts, and skips the shared
    portion of prefill entirely. Refcount-0 registered pages are RETAINED
    (LRU) and reclaimed only under pool pressure, so a popular system
    prompt stays hot across non-overlapping requests.
  - **int8 page payloads** (`kv_dtype="int8"`): page rows quantized with
    `ops.quant_comm`'s per-256-element block quantizer (EQuARX layout,
    round 12) — one f32 scale per 256 elements of the flattened
    `[P, D]` row per head, payload int8 — for ~4x pages per HBM byte vs
    f32 (~2x vs bf16). Quantization is lossy by construction, so int8 KV
    is gated by a token-level tolerance test (tests/test_paged.py),
    mirroring the round-12 loss-trajectory gate; f32/bf16 page storage at
    the matching compute dtype stays token-for-token exact.

  - **Pages by kind** (PR 27): what a page holds is the model's statement
    (`PageKind`, from `tpukit.model.family(cfg).page_kinds`). The GPT block
    keeps the one kind above. The latent family (tpukit/model/latent.py)
    keeps ROW pools `[L, NP, P, W]` — one latent row and one indexer key a
    token in its full layers, behind the same kind of block table — and a
    second kind for its window layers whose table is a RING: a request
    holds at most `ceil(window / P) + 1` of those pages, logical page `j`
    at column `j % R`, so the page that fell behind the window is the next
    one written. The engine keeps one pool, one `PageAllocator` and one
    host block table per kind; `pool_bytes` counts them all.

Write-safety invariants (everything here leans on them):

  1. A slot's WRITABLE pages are exclusively owned. Shared (registered)
     pages are capped at `(prompt_len - 1) // P` — the page holding
     position `prompt_len - 1` is always private, because the first
     decode tick re-forwards the last prompt token and rewrites that
     position's K/V (identical values, but a write nonetheless — and
     under int8 a block REQUANTIZATION, which must never touch a page
     another slot reads).
  2. Masked rows (inactive/free slots, padded admit lanes) write to page
     0. The engine zeroes a freed slot's block-table row, so even a stale
     in-flight write after eviction lands in the null page, never in a
     page the allocator has re-issued.
  3. Reads beyond a slot's logical cursor hit garbage (the null page, an
     unwritten tail, a recycled page's old contents) — and are masked by
     the causal `key_pos <= q_pos` window exactly like the ring path's
     stale-tail garbage, which softmax turns into exact zeros. Same
     argument, same tests.

Observability (round 20): the pool itself emits nothing — page claims and
cross-pool copies during a disaggregated-prefill handoff are timed by the
ROUTER (`fleet.FleetRouter._adopt` emits the `handoff` span event with
`claim_s`/`copy_s`/`pages` into the request's trace; tpukit/obs/trace.py),
keeping this module free of telemetry plumbing: it stays a pure
allocator + layout library, and handoff cost is attributed where the
decision was made.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict, deque

import jax
import jax.numpy as jnp

from tpukit.ops import quant_comm

KV_DTYPES = ("f32", "bf16", "int8")

_STORAGE = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def storage_dtype(kv_dtype: str):
    """jnp storage dtype of a non-quantized page pool."""
    if kv_dtype not in _STORAGE:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
    return _STORAGE[kv_dtype]


def validate_kv_layout(cfg, page_size: int, kv_dtype: str,
                       block: int = quant_comm.DEFAULT_BLOCK) -> None:
    """Named construction-time rejection of layouts that would otherwise
    surface as opaque XLA shape errors deep inside the quantizer: int8
    pages quantize each head's flattened `[P, D]` row in `block`-element
    blocks, so the row must tile exactly."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
    if kv_dtype == "int8":
        row = page_size * cfg.head_dim
        if row % block:
            raise ValueError(
                f"kv_dtype=int8 requires the page payload per head "
                f"(page_size {page_size} x head_dim {cfg.head_dim} = {row} "
                f"elements) to be a multiple of quant_comm's {block}-element "
                f"quant block — use a page size that tiles into {block}s "
                f"(e.g. page_size {-(-block // cfg.head_dim)})"
            )


def scale_blocks(cfg, page_size: int, block: int = quant_comm.DEFAULT_BLOCK) -> int:
    """f32 scales per (page, head) row of an int8 pool."""
    return (page_size * cfg.head_dim) // block


def init_paged_cache(cfg, num_pages: int, page_size: int, pages_per_slot: int,
                     slots: int, kv_dtype: str = "f32") -> dict:
    """The paged-cache pytree the serve programs thread: K/V pools
    `[L, num_pages, H, P, D]` (int8 adds per-row scale sidecars
    `[L, num_pages, H, blocks]`) plus the block tables `[N, pages_per_slot]`
    (all zeros = every slot dereferences the null page)."""
    validate_kv_layout(cfg, page_size, kv_dtype)
    shape = (cfg.num_layers, num_pages, cfg.heads, page_size, cfg.head_dim)
    bt = jnp.zeros((slots, pages_per_slot), jnp.int32)
    if kv_dtype == "int8":
        nb = scale_blocks(cfg, page_size)
        sshape = (cfg.num_layers, num_pages, cfg.heads, nb)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "ks": jnp.zeros(sshape, jnp.float32),
            "vs": jnp.zeros(sshape, jnp.float32),
            "bt": bt,
        }
    dt = storage_dtype(kv_dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt), "bt": bt}


def pool_bytes(cfg, num_pages, page_size: int, kv_dtype: str) -> int:
    """Closed-form HBM bytes of every page pool `cfg`'s model keeps, by its
    own statement of its page kinds (`tpukit.model.family(cfg).page_kinds`):
    layers x pages x page_size x bytes a token, summed over the kinds. For
    the GPT block that is the K+V pools (the equal-HBM bench math: int8 pays
    1 byte per element plus the 4-byte-per-block f32 scale sidecar, i.e.
    `packed_bytes` per (page, head) row). `num_pages` is one count for a
    model with one kind, else `{block-table key: pages}`."""
    from tpukit.model import family  # lazy: tpukit.model imports this module's users

    kinds = family(cfg).page_kinds(cfg, page_size, kv_dtype)
    if not isinstance(num_pages, dict):
        if len(kinds) != 1:
            raise ValueError(
                f"{type(cfg).__name__} keeps {len(kinds)} page kinds "
                f"({[k.table for k in kinds]}): give pages per kind as a dict"
            )
        num_pages = {kinds[0].table: num_pages}
    return sum(k.layers * num_pages[k.table] * k.page_bytes for k in kinds)


@dataclasses.dataclass(frozen=True)
class PageKind:
    """One kind of page a model keeps: which block table addresses it, how
    many layers share that table, what a page of ONE layer weighs, and how
    many pages a request can ever hold of it. `ring_pages` 0: a request holds
    a page for every `page_size` tokens of its worst case, logical page `j`
    at column `j` of its block-table row. `ring_pages` R > 0 (a window
    layer's store): it holds at most R, logical page `j` at column `j % R`,
    so a page that has fallen behind the window is the next one written."""

    table: str
    layers: int
    page_bytes: int
    ring_pages: int = 0

    def pages_for(self, tokens: int, page_size: int) -> int:
        need = -(-tokens // page_size)
        return min(need, self.ring_pages) if self.ring_pages else need


def gpt_page_kinds(cfg, page_size: int, kv_dtype: str) -> tuple[PageKind, ...]:
    """The GPT block's one kind: K and V rows of every head, every layer
    behind the one block table."""
    per_head_row = page_size * cfg.head_dim
    if kv_dtype == "int8":
        row_bytes = quant_comm.packed_bytes(per_head_row)
    else:
        row_bytes = per_head_row * jnp.dtype(storage_dtype(kv_dtype)).itemsize
    return (PageKind("bt", cfg.num_layers, 2 * cfg.heads * row_bytes),)


# -- device-side page ops (called per layer from gpt.forward_cached, each on
# the whole stacked pool and the layer's index) -----------------------------


@jax.named_scope("kv_gather")
def gather_view(pool, scales, layer, bt, out_dtype):
    """Dereference the block tables: layer `layer`'s pages of the stacked
    `pool [L, NP, H, P, D]` gathered through `bt [N, MP]` into the
    `[N, H, MP*P, D]` per-row K (or V) view the round-14 vector-cursor
    attention consumes — ONE gather on `(layer, bt)`, the layer's pool is
    never sliced out of the stack. Logical position `q` of row `b` lives at
    `view[b, :, q, :]` == page `bt[b, q // P]`, offset `q % P` — the one
    indirection of the paged design. int8 pools dequantize after the gather
    (per-row blocks, `quant_comm` layout)."""
    v = pool[layer, bt]  # [N, MP, H, P, D] — gather on the (unsharded) layer and page axes
    n, mp, h, p, d = v.shape
    if scales is not None:
        # dequantize with the head axis PRESERVED (the pools shard heads
        # over `model`; merging H into a rows axis would force a GSPMD
        # reshard — the comm-free audit would break)
        s = scales[layer, bt]  # [N, MP, H, blocks]
        v = quant_comm.dequantize_blocks(
            v.reshape(n, mp, h, p * d), s
        ).reshape(n, mp, h, p, d)
    return v.astype(out_dtype).transpose(0, 2, 1, 3, 4).reshape(n, h, mp * p, d)


@jax.named_scope("kv_write")
def write_token(pool, scales, layer, bt, start, val, write_mask):
    """Decode-tick write-back into the stacked `pool [L, NP, H, P, D]`: row
    `b`'s freshly computed K (or V) `val [N, H, D]` lands at logical
    position `start[b]` of layer `layer` — page `bt[b, start // P]`, offset
    `start % P` — as ONE scatter at `(layer, pids, :, off, :)`. The stack
    is the scatter's operand, so a caller whose stack dies there (the
    decode quantum's loop carry) has it updated in place; nothing is
    sliced out or restacked. Rows with `write_mask` False are routed to
    the null page (invariant 2 above): an inactive or prefilling slot's
    re-forward must never touch a real page.

    f32/bf16 pools scatter the single position; int8 pools gather the
    touched page rows `(layer, pids)`, dequantize, insert the exact new
    value, REQUANTIZE the rows (the block scale may move — which is why
    shared pages are never writable, invariant 1) and scatter them back at
    `(layer, pids)`. Writable pages are exclusive per slot, so the
    scatter's row indices never collide except on the null page, where any
    winner is garbage by design."""
    n = start.shape[0]
    p = pool.shape[3]
    page = start // p
    off = start % p
    pids = jnp.take_along_axis(bt, page[:, None], axis=1)[:, 0]
    pids = jnp.where(write_mask, pids, 0)
    if scales is None:
        return pool.at[layer, pids, :, off, :].set(val.astype(pool.dtype)), None
    h, d = pool.shape[2], pool.shape[4]
    rows = pool[layer, pids]  # [N, H, P, D] int8
    srows = scales[layer, pids]  # [N, H, blocks]
    # head axis preserved through the quantizer (sharding — gather_view)
    deq = quant_comm.dequantize_blocks(
        rows.reshape(n, h, p * d), srows
    ).reshape(n, h, p, d)
    hit = jax.lax.broadcasted_iota(jnp.int32, (n, 1, p, 1), 2) == off[:, None, None, None]
    deq = jnp.where(hit, val[:, :, None, :].astype(jnp.float32), deq)
    q, s = quant_comm.quantize_blocks(deq.reshape(n, h, p * d))
    return (
        pool.at[layer, pids].set(q.reshape(n, h, p, d)),
        scales.at[layer, pids].set(s),
    )


@jax.named_scope("kv_write")
def write_pages(pool, scales, layer, bt, start, vals, write_mask):
    """Prefill-chunk write-back into the stacked `pool [L, NP, H, P, D]`:
    `vals [N, H, C, D]` covers logical positions `[start[b], start[b] + C)`
    per row, with `start` page-aligned and C a page multiple (the engine's
    chunking contract) — so the write is whole pages of layer `layer`, ONE
    scatter at `(layer, pids)` with a row per (lane, chunk-page). Masked
    lanes route to the null page. Chunk positions beyond a lane's
    allocation dereference block-table zeros and also land in the null
    page — bucket-pad garbage never occupies a real page."""
    n, h, c, d = vals.shape
    p = pool.shape[3]
    npg = c // p
    first = start // p
    j = jnp.arange(npg, dtype=start.dtype)
    pids = jnp.take_along_axis(bt, first[:, None] + j[None, :], axis=1)  # [N, npg]
    pids = jnp.where(write_mask[:, None], pids, 0).reshape(-1)
    rows = (
        vals.reshape(n, h, npg, p, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(n * npg, h, p, d)
    )
    if scales is None:
        return pool.at[layer, pids].set(rows.astype(pool.dtype)), None
    q, s = quant_comm.quantize_blocks(  # head axis preserved (sharding)
        rows.astype(jnp.float32).reshape(n * npg, h, p * d)
    )
    return (
        pool.at[layer, pids].set(q.reshape(n * npg, h, p, d)),
        scales.at[layer, pids].set(s),
    )


# -- row pools (the latent family, tpukit/model/latent.py) -------------------
# A pool `[L, NP, P, W]` keeps ONE row of width W a token and layer (a latent
# and its shared rope key, an indexer key) instead of K and V rows per head.
# Same discipline as above: the stack is indexed `(layer, page)` where it
# lies, page 0 is the null page, masked writes go there.


def init_row_pool(layers: int, num_pages: int, page_size: int, width: int, kv_dtype: str):
    return jnp.zeros((layers, num_pages, page_size, width), storage_dtype(kv_dtype))


def logical_pages(bt, first, count: int, ring: int = 0):
    """Page ids of logical pages `first[b] + [0, count)` of each row of `bt
    [N, MP]`: column `j`, or `j % ring` of a ring (negative `j`, before the
    request began, wraps to some column too: the caller masks those keys by
    position). `[N, count]`."""
    j = first[:, None] + jnp.arange(count, dtype=first.dtype)[None, :]
    col = jnp.mod(j, ring) if ring else jnp.clip(j, 0, bt.shape[1] - 1)
    return jnp.take_along_axis(bt, col, axis=1)


@jax.named_scope("kv_write")
def write_row(pool, layer, pids, off, val):
    """Decode-tick write: `val [N, W]` at `(layer, pids[b], off[b])`, one
    scatter on the stack. `pids` already routes masked rows to page 0."""
    return pool.at[layer, pids, off, :].set(val.astype(pool.dtype))


@jax.named_scope("kv_write")
def write_row_pages(pool, layer, pids, vals):
    """Prefill-chunk write: `vals [N, C, W]` as the whole pages `pids [N,
    C // P]` of layer `layer`, one scatter of `N x C/P` page rows."""
    n, c, w = vals.shape
    p = pool.shape[2]
    return pool.at[layer, pids.reshape(-1)].set(
        vals.reshape(n * (c // p), p, w).astype(pool.dtype))


# -- page handoff (round 19, disaggregated prefill) --------------------------
# The ONE spelling of the device-to-device page copy the fleet's
# prefill->decode handoff rides (tpukit/serve/fleet.py): extract gathers the
# source pool's page rows (every layer, every head) into a dense block, the
# caller moves the block between the two engines' device subsets with ONE
# jax.device_put at the destination pool's layout, and insert scatters it
# into the destination pool. Works on K/V pools ([L, NP, H, P, D]) AND int8
# scale sidecars ([L, NP, H, blocks]) — anything with the page axis at
# position 1. `ids` is traced, so the compile count is one per padded id
# width (the caller pads to powers of two: src pads by repeating the last id
# — re-extracting a page is idempotent — and dst pads with 0, the null-page
# sink, write-safety invariant 2).


@jax.jit
def extract_pages(pool, ids):
    """`pool[:, ids]` — the page rows to hand off, `[L, n, ...]`."""
    return pool[:, ids]


@jax.jit
def insert_pages(pool, ids, block):
    """Scatter a handed-off block into `pool` at page rows `ids`. The
    destination pages are freshly allocated (exclusively owned, refcount
    1) or the null page (pad), so rows never collide with a reader."""
    return pool.at[:, ids].set(block.astype(pool.dtype))


# -- host-side page allocator + shared-prefix registry ----------------------


@dataclasses.dataclass
class PageStats:
    """Counters the engine folds into its serve windows."""

    prefix_hits: int = 0
    prefix_pages_reused: int = 0
    prefix_lookups: int = 0
    reclaimed: int = 0


class PageAllocator:
    """Host-side bookkeeping for the page pool: a free list over pages
    `1..num_pages-1` (0 is the null page), per-page refcounts, and the
    shared-prefix registry.

    The registry is a radix-style chain keyed by `(parent_page_id,
    chunk_tokens)` — a page is reachable only through its registered
    parent, so matching is exact (token tuples, no hash collisions) and a
    freed parent automatically orphans its subtree (which is purged, so a
    reallocated page id can never be matched under stale content).
    Registered pages whose refcount drops to 0 are RETAINED in an LRU and
    reclaimed only when an allocation would otherwise fail — a popular
    prefix survives gaps between requests."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError(
                f"num_pages={num_pages} must be >= 2 (page 0 is the "
                f"reserved null page)"
            )
        self.num_pages = num_pages
        self.page_size = page_size
        self._free = deque(range(1, num_pages))
        self.refcount = [0] * num_pages
        self._registry: dict[tuple, int] = {}  # (parent, chunk) -> page
        self._key_of: dict[int, tuple] = {}  # page -> its registry key
        self._parent: dict[int, int] = {}  # page -> parent page (0 = root)
        self._children: dict[int, set] = {}  # page -> registered children
        self._retained: OrderedDict[int, None] = OrderedDict()  # refcount-0 LRU
        self.stats = PageStats()

    # ---- accounting ----

    @property
    def free_pages(self) -> int:
        """Pages allocatable WITHOUT evicting retained prefix pages."""
        return len(self._free)

    @property
    def available_pages(self) -> int:
        """Pages an `alloc` could produce (free + reclaimable retained)."""
        return len(self._free) + len(self._retained)

    @property
    def live_pages(self) -> int:
        """Pages referenced by at least one slot."""
        return (self.num_pages - 1) - len(self._free) - len(self._retained)

    @property
    def occupancy(self) -> float:
        """Live fraction of the allocatable pool."""
        return self.live_pages / max(self.num_pages - 1, 1)

    # ---- allocate / release ----

    def alloc(self, n: int) -> list[int] | None:
        """`n` exclusive pages (refcount 1 each), or None if the pool
        cannot cover them even after reclaiming retained prefix pages
        (LRU order) — the admission-control signal. Feasibility is
        checked BEFORE any reclaim: a doomed allocation must not purge
        the retained prefix registry on its way to failing (the caller
        retries the same admission next iteration, and every hit it
        would have had is gone)."""
        if len(self._free) + len(self._retained) < n:
            return None
        while len(self._free) < n and self._retained:
            self._purge(next(iter(self._retained)))
            self.stats.reclaimed += 1
        if len(self._free) < n:
            return None
        out = [self._free.popleft() for _ in range(n)]
        for p in out:
            self.refcount[p] = 1
        return out

    def claim(self, pages: list[int]) -> None:
        """Take a reader reference on shared pages (a prefix hit). A
        retained page comes back live."""
        for p in pages:
            if p in self._retained:
                del self._retained[p]
            self.refcount[p] += 1

    def release(self, pages: list[int]) -> None:
        """Drop one reference per page (eviction). A registered page at
        refcount 0 is retained for future prefix hits; an unregistered one
        returns to the free list."""
        for p in pages:
            if p <= 0:
                continue
            self.refcount[p] -= 1
            if self.refcount[p] < 0:
                raise AssertionError(f"page {p} refcount went negative")
            if self.refcount[p] == 0:
                if p in self._key_of:
                    self._retained[p] = None
                else:
                    self._free.append(p)

    def _purge(self, pid: int) -> None:
        """Remove `pid`'s registration (and its whole registered subtree —
        children are only reachable through the parent). Retained pages in
        the subtree return to the free list; live ones just lose their
        registration and free normally at their last release."""
        key = self._key_of.pop(pid, None)
        if key is not None:
            self._registry.pop(key, None)
        parent = self._parent.pop(pid, None)
        if parent is not None and parent in self._children:
            self._children[parent].discard(pid)
        if pid in self._retained:
            del self._retained[pid]
            self._free.append(pid)
        for child in list(self._children.pop(pid, ())):
            self._purge(child)

    # ---- shared-prefix registry ----

    def _chunk(self, ids, i: int) -> tuple:
        p = self.page_size
        return tuple(int(t) for t in ids[i * p : (i + 1) * p])

    def lookup_prefix(self, ids, max_pages: int) -> list[int]:
        """Longest registered chain matching `ids` at page granularity,
        capped at `max_pages` (the caller passes `(prompt_len - 1) // P` —
        invariant 1: the page holding the last prompt position must stay
        private). Returned pages are NOT yet claimed."""
        self.stats.prefix_lookups += 1
        out: list[int] = []
        parent = 0
        for i in range(max_pages):
            pid = self._registry.get((parent, self._chunk(ids, i)))
            if pid is None:
                break
            out.append(pid)
            parent = pid
        return out

    def register(self, ids, pages: list[int]) -> None:
        """Publish `pages[i] = K/V of ids[i*P:(i+1)*P]` into the registry
        (called once a slot's prefill completes — the pages are final and
        read-only from here on). Already-registered chunks keep their
        first registration; our duplicate page stays private and frees
        normally, while deeper chunks chain from the canonical page so one
        popular prefix converges to one chain."""
        parent = 0
        for i, pid in enumerate(pages):
            key = (parent, self._chunk(ids, i))
            existing = self._registry.get(key)
            if existing is not None:
                parent = existing
                continue
            if pid in self._key_of:  # already published under another chain
                parent = pid
                continue
            self._registry[key] = pid
            self._key_of[pid] = key
            self._parent[pid] = parent
            self._children.setdefault(parent, set()).add(pid)
            parent = pid

    def registered_pages(self) -> int:
        return len(self._key_of)
