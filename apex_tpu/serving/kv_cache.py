"""Block-paged KV cache: device layout + host block-pool bookkeeping.

The serving cache is a fixed pool of ``num_blocks`` blocks of
``block_size`` tokens each, shared by every in-flight request.  A
request owns an ordered list of block ids (its *block table*); growing
a sequence past a block boundary appends one block from the free list,
finishing a request returns its blocks.  Nothing is ever moved or
compacted — **defrag-free paging**: the flash-decode kernel gathers
pages through the block table (scalar-prefetched index map), so block
ids need no spatial locality, and admission/eviction cost is O(pages
touched), never O(cache).

Two cleanly separated halves:

* :class:`PagedKVCache` — the DEVICE state: one k and one v block
  array a layer, ``(nb, hk, bs, dk)`` each (tuples of ``num_layers``
  arrays), plus optional int8 per-row scales ``(nb, h, bs)``; the
  **latent** kind (``KVCacheConfig.value_dim``, latent attention) one
  ``(nb, 1, bs, d_latent)`` array a layer and no v array, a token's row
  ``[c_kv ; k_rope]`` being its key and, in its first ``value_dim``
  values, its value; the **pooled** kind (``KVCacheConfig.window``, EVA
  attention) the same k and v arrays, whose pages hold either a window's
  rows or one pooled row a closed page.  A pytree, threaded through the jitted prefill/decode steps and
  **donated** every step, every leaf its own buffer (the same carry
  discipline as the scan driver's amp state — the cache is the
  largest buffer in the serving process, double-buffering it halves
  capacity).  ``hk``/``dk`` follow the d=64 head-pair packing decision
  (:func:`apex_tpu.ops.flash_decode.use_decode_head_packing`) so the
  kernel and the layout can never disagree.  A layer's array is what
  the decode kernel reads, as it lies: nothing slices or transposes it
  on the way, because every write is **page-granular** (gather the
  touched pages, put the new rows in, scatter whole pages back;
  :func:`plan_page_write` once a step, :func:`write_token_kv` a
  layer).  A
  row scatter ``arr.at[blocks, :, offsets, :]`` would make XLA keep
  the array with ``hk`` minor to ``bs`` and copy all of it to the
  kernel's layout and back on every step.  The page write rewrites a
  whole page from what it gathered, so **no two rows of one step may
  write the same page**, the dump page apart: live rows never do
  (:meth:`KVCacheManager.cow_for_append` makes a shared page private
  before an append).
* :class:`KVCacheManager` — the HOST bookkeeping: free list, per-
  request tables and lengths.  Pure Python, no device work; the engine
  consults it between jitted steps (the continuous-batching boundary).

Block 0 is reserved as the **dump page**: it is never handed to a
request, block-table padding points at it, and inactive batch rows
point their writes at it (a page write addressed there puts the dump
page back as it found it) — so a bucketed decode step needs no batch
mask and a dead page read contributes exactly 0.

Storage dtype (``APEX_TPU_SERVE_KV_DTYPE``): ``model`` stores k/v in
the model compute dtype, ``bf16`` forces bfloat16 (the O4/O5-native
choice), ``int8`` stores weight-only-quantized rows with per-token,
per-head fp32 scales — appending never requantizes history, and the
kernel dequantizes per page in VMEM (docs/api/serving.md#kv-dtype).
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..ops.flash_decode import use_decode_head_packing

__all__ = ["KVCacheConfig", "PagedKVCache", "KVCacheManager",
           "PrefixMatch", "CachePoolExhausted", "init_cache",
           "PageWrite", "plan_page_write", "write_token_kv",
           "write_prefill_kv", "quantize_kv_rows",
           "prefix_chain_keys", "DUMP_BLOCK"]

# block 0: never allocated, pads every block table, absorbs inactive
# rows' writes.  Reads of it are always masked to an exact 0 weight.
DUMP_BLOCK = 0

_KV_DTYPES = ("model", "bf16", "int8")


class CachePoolExhausted(RuntimeError):
    """The block pool cannot cover a requested allocation — the
    admission-control signal (callers check :meth:`KVCacheManager.
    can_admit` first; racing past it raises this)."""


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Static shape/dtype plan for one paged cache."""

    num_layers: int
    num_heads: int
    head_dim: int
    num_blocks: int          # INCLUDING the reserved dump block
    block_size: int
    kv_dtype: str = "model"  # 'model' | 'bf16' | 'int8'
    model_dtype: jnp.dtype = jnp.float32
    # the LATENT kind (latent attention, MLA): one array a layer and no
    # v array.  A cached token is one row of ``head_dim`` values shared
    # by every query head (``num_heads`` is 1), ``[c_kv ; k_rope]``,
    # whose first ``value_dim`` are also its value: a page is read once
    # for both.  None: the k and v arrays of the module docstring.
    value_dim: Optional[int] = None
    # the POOLED kind (EVA attention): a layer keeps the k and v rows of
    # the current ``window``-aligned window alone and, for every closed
    # page before it, ONE pooled (k, v) row -- a page of ``block_size``
    # rows pools to a row of the same shape, so window pages and summary
    # pages are the same arrays and one pool, and a request holds two
    # lists (:class:`KVCacheManager`).  None: every position keeps its row.
    window: Optional[int] = None

    def __post_init__(self):
        if self.kv_dtype not in _KV_DTYPES:
            raise ValueError(f"kv_dtype {self.kv_dtype!r} not in "
                             f"{_KV_DTYPES}")
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "reserved dump page)")
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        if self.latent:
            if self.num_heads != 1 or not 0 < self.value_dim <= self.head_dim:
                raise ValueError(
                    f"a latent cache holds one row a token (num_heads 1, "
                    f"not {self.num_heads}) whose first value_dim "
                    f"({self.value_dim}) of head_dim ({self.head_dim}) "
                    f"values are its value")
            if self.quantized:
                raise ValueError(
                    "the latent cache has no int8 storage yet: a latent "
                    "row is every head's key and value at once, and one "
                    "scale a row is unproven there")
        if self.pooled:
            if self.latent or self.quantized:
                raise ValueError(
                    "the pooled cache (window pages beside one pooled row "
                    "a closed page) has no latent and no int8 storage yet")
            if self.packed:
                raise ValueError(
                    "the pooled cache pools a page a head: it has no "
                    "head-pair packed layout (d=64 pairs) yet")
            if self.window < 1 or self.window % self.block_size ** 2:
                raise ValueError(
                    f"a pooled cache's window ({self.window}) is whole "
                    f"summary pages: a multiple of block_size^2 "
                    f"({self.block_size ** 2}), a page of rows pooling to "
                    f"one row of a page")

    @property
    def latent(self) -> bool:
        return self.value_dim is not None

    @property
    def pooled(self) -> bool:
        return self.window is not None

    @property
    def window_pages(self) -> int:
        """Pages of a full window (the pooled kind)."""
        return self.window // self.block_size

    @property
    def window_summary_pages(self) -> int:
        """Summary pages a closed window's pooled rows fill."""
        return self.window // self.block_size ** 2

    @property
    def packed(self) -> bool:
        return use_decode_head_packing(self.num_heads, self.head_dim)

    @property
    def storage_dtype(self):
        if self.kv_dtype == "int8":
            return jnp.int8
        if self.kv_dtype == "bf16":
            return jnp.bfloat16
        return self.model_dtype

    @property
    def quantized(self) -> bool:
        return self.kv_dtype == "int8"

    @property
    def kv_shape(self):
        """(nb, hk, bs, dk) of ONE layer's k (or v) array — the packed
        storage head axes; the cache holds ``num_layers`` of each."""
        h, d = self.num_heads, self.head_dim
        hk, dk = (h // 2, 2 * d) if self.packed else (h, d)
        return (self.num_blocks, hk, self.block_size, dk)

    @property
    def scale_shape(self):
        """(nb, h, bs) of one layer's scales — GLOBAL head order."""
        return (self.num_blocks, self.num_heads, self.block_size)

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1

    def blocks_for(self, length: int) -> int:
        """The most blocks a request holds on its way to ``length``
        positions: its reservation.  Every position's page; the pooled
        kind ``min(ceil(T / bs), window pages) + ceil(T / bs^2)``, the
        pages of one window and a summary row a page."""
        length = max(int(length), 1)
        pages = -(-length // self.block_size)
        if not self.pooled:
            return pages
        return min(pages, self.window_pages) + -(-pages // self.block_size)

    def table_pages(self, length: int) -> int:
        """The columns a block table needs for a row of up to ``length``
        positions: its pages, or for the pooled kind the summary pages
        of the closed windows and then the window's own."""
        length = max(int(length), 1)
        pages = -(-length // self.block_size)
        if not self.pooled:
            return pages
        return min(pages, self.window_pages) \
            + (length - 1) // self.window * self.window_summary_pages

    def cache_nbytes(self) -> int:
        per = np.dtype(self.storage_dtype).itemsize
        n = (1 if self.latent else 2) * int(np.prod(self.kv_shape)) * per
        if self.quantized:
            n += 2 * int(np.prod(self.scale_shape)) * 4
        return self.num_layers * n


class PagedKVCache(NamedTuple):
    """Device half of the cache (a pytree — jit/donation friendly):
    ``num_layers`` arrays a field, one a layer."""

    k: Tuple[jnp.ndarray, ...]               # each (nb, hk, bs, dk)
    v: Optional[Tuple[jnp.ndarray, ...]]     # None: the latent kind
    k_scale: Optional[Tuple[jnp.ndarray, ...]]  # each (nb, h, bs) fp32
    v_scale: Optional[Tuple[jnp.ndarray, ...]]

    def layer(self, i: int):
        """Layer ``i``'s (k, v, k_scale, v_scale) arrays."""
        return (self.k[i], None if self.v is None else self.v[i],
                None if self.k_scale is None else self.k_scale[i],
                None if self.v_scale is None else self.v_scale[i])

    def with_layer(self, i: int, k, v, k_scale=None,
                   v_scale=None) -> "PagedKVCache":
        """This cache with layer ``i``'s arrays replaced; every other
        leaf passes through untouched."""
        def put(leaves, x):
            if leaves is None:
                return None
            return leaves[:i] + (x,) + leaves[i + 1:]

        return PagedKVCache(put(self.k, k), put(self.v, v),
                            put(self.k_scale, k_scale),
                            put(self.v_scale, v_scale))


def init_cache(config: KVCacheConfig) -> PagedKVCache:
    """All-zero cache (zeros are the safe dead-page filler: even an
    unmasked read of a never-written row contributes finite values).
    Every leaf is a DISTINCT buffer: the cache pytree is donated every
    step, and aliased leaves would donate the same buffer twice."""
    def leaves(shape, dtype):
        return tuple(jnp.zeros(shape, dtype)
                     for _ in range(config.num_layers))

    k = leaves(config.kv_shape, config.storage_dtype)
    if config.latent:
        return PagedKVCache(k, None, None, None)
    v = leaves(config.kv_shape, config.storage_dtype)
    if config.quantized:
        return PagedKVCache(k, v,
                            leaves(config.scale_shape, jnp.float32),
                            leaves(config.scale_shape, jnp.float32))
    return PagedKVCache(k, v, None, None)


def quantize_kv_rows(x: jnp.ndarray):
    """Per-row symmetric int8: ``x`` (..., d) -> (int8 values,
    (...,) fp32 scales).  Each cached token row quantizes against its
    own amax, so appends never touch history."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _to_storage(x, config: KVCacheConfig):
    """(..., h, d) new rows -> (storage values (..., hk, dk),
    scales (..., h) | None) per the cache layout."""
    if config.quantized:
        q, scale = quantize_kv_rows(x)
        if config.packed:
            q = q.reshape(*q.shape[:-2], config.num_heads // 2,
                          2 * config.head_dim)
        return q, scale
    if config.packed:
        x = x.reshape(*x.shape[:-2], config.num_heads // 2,
                      2 * config.head_dim)
    return x.astype(config.storage_dtype), None


class PageWrite(NamedTuple):
    """Where one step's new tokens go, page by page: the same for every
    layer, so a step plans once (:func:`plan_page_write`) and writes a
    layer at a time (:func:`write_token_kv`).  Row ``b`` touches ``P``
    pages: ``slot_blocks`` (b, P) int32 is each one's block id (the
    dump page where nothing lands), and ``src`` / ``hit`` (b, P * bs)
    say which of the row's tokens lands on in-page row ``o`` of page
    ``s``, at ``[b, s * bs + o]``, and whether one does."""

    slot_blocks: jnp.ndarray
    src: jnp.ndarray
    hit: jnp.ndarray


def plan_page_write(blocks: jnp.ndarray, offsets: jnp.ndarray,
                    block_size: int) -> PageWrite:
    """The :class:`PageWrite` of a step's write slots.

    One token a row (the decode step): ``blocks``/``offsets`` (b,)
    int32, each row's current page and in-page slot.  A chunk a row
    (the extend step): (b, t).  Tokens whose block is the dump page
    (inactive rows, a chunk's padding, positions past a row's budget)
    are not written.  **A row's written tokens sit at contiguous
    positions**, as a chunk's do, so they cover at most ``P = (t + bs -
    2) // bs + 1`` pages, counted from the page of the first of them;
    only that token's offset is read."""
    if blocks.ndim == 1:
        blocks, offsets = blocks[:, None], offsets[:, None]
    bs = block_size
    b, t = blocks.shape
    n_slots = (t + bs - 2) // bs + 1
    written = blocks != DUMP_BLOCK
    first = jnp.argmax(written, axis=1).astype(jnp.int32)
    start = first - jnp.take_along_axis(
        offsets.astype(jnp.int32), first[:, None], axis=1)[:, 0]
    src = start[:, None] + jnp.arange(n_slots * bs, dtype=jnp.int32)
    inside = (src >= 0) & (src < t)
    src = jnp.clip(src, 0, t - 1)
    hit = inside & jnp.take_along_axis(written, src, axis=1)
    landed = jnp.where(hit, jnp.take_along_axis(blocks, src, axis=1),
                       DUMP_BLOCK)
    # real block ids are > DUMP_BLOCK: a page's id is its tokens' max
    slot_blocks = landed.reshape(b, n_slots, bs).max(axis=2)
    return PageWrite(slot_blocks.astype(jnp.int32), src, hit)


def _write_pages(arr, new, plan: PageWrite):
    """``arr`` (nb, hk, bs, *tail) with the rows of ``new`` (b, t, hk,
    *tail) put in per ``plan``: gather the touched pages, select the
    new rows in, scatter whole pages back."""
    b, n_slots = plan.slot_blocks.shape
    bs, tail = arr.shape[2], arr.ndim - 3
    pages = arr[plan.slot_blocks]            # (b, P, hk, bs, *tail)
    if new.shape[1] == 1:
        rows = new[:, :, :, None]            # the one token, every row
    else:
        rows = jnp.take_along_axis(
            new, plan.src.reshape(b, n_slots * bs,
                                  *(1,) * (new.ndim - 2)),
            axis=1)                          # (b, P*bs, hk, *tail)
        rows = jnp.moveaxis(
            rows.reshape(b, n_slots, bs, *new.shape[2:]), 2, 3)
    pages = jnp.where(plan.hit.reshape(b, n_slots, 1, bs, *(1,) * tail),
                      rows, pages)
    return arr.at[plan.slot_blocks.reshape(-1)].set(
        pages.reshape(b * n_slots, *arr.shape[1:]))


def write_token_kv(cache: PagedKVCache, config: KVCacheConfig,
                   layer: int, k_new: jnp.ndarray, v_new: jnp.ndarray,
                   plan: PageWrite) -> PagedKVCache:
    """Write each batch row's new token(s) into layer ``layer``'s page
    slots, a page at a time.

    ``k_new``/``v_new`` in model dtype, (b, h, d) for one token a row
    (the decode step) or (b, t, h, d) for a chunk a row (the extend
    step); ``plan`` is :func:`plan_page_write` of the step's write
    slots.  The latent kind takes its rows ``(..., 1, head_dim)`` as
    ``k_new`` and ``v_new`` None.

    The write is page-granular (module docstring): the touched pages
    are gathered, the new rows selected in, and whole pages scattered
    back, so the layer's array keeps the decode kernel's layout.
    **Precondition: no two rows of one step write the same page.**
    Live rows never do: a row owns the pages it writes, and a shared
    prefix page is made private first (:meth:`KVCacheManager.
    cow_for_append` / :meth:`~KVCacheManager.make_private`); a chunk's
    several tokens on one page are put in together.  Pages nothing
    lands on resolve to the dump page and go back as they came.

    Per-layer because the decode step interleaves write -> attend
    inside its layer loop (the new token attends to itself through the
    cache); only layer ``layer``'s leaves change.  Traced code — runs
    inside the jitted step; the cache argument is donated by the caller
    so the page scatter is in-place on device."""
    if k_new.ndim == 3:
        k_new = k_new[:, None]
        v_new = None if v_new is None else v_new[:, None]
    kq, ks = _to_storage(k_new, config)
    if config.latent:          # the rows ARE the values: no v array
        return cache.with_layer(
            layer, _write_pages(cache.k[layer], kq, plan), None)
    vq, vs = _to_storage(v_new, config)
    kc, vc, kc_scale, vc_scale = cache.layer(layer)
    kc = _write_pages(kc, kq, plan)
    vc = _write_pages(vc, vq, plan)
    if config.quantized:
        kc_scale = _write_pages(kc_scale, ks, plan)
        vc_scale = _write_pages(vc_scale, vs, plan)
    return cache.with_layer(layer, kc, vc, kc_scale, vc_scale)


def write_prefill_kv(cache: PagedKVCache, config: KVCacheConfig,
                     layer: int, k_all: jnp.ndarray,
                     v_all: jnp.ndarray,
                     blocks: jnp.ndarray) -> PagedKVCache:
    """Scatter a prefilled prompt's whole k/v for one layer into its
    pages.

    ``k_all``/``v_all`` (s_pad, h, d) (the latent kind: its rows and
    None) with ``s_pad = len(blocks) *
    block_size``; ``blocks`` (n_pages,) int32 — pages past the
    request's owned tail point at the dump block (duplicate dump
    writes race harmlessly: the dump page is never read unmasked)."""
    s_pad, h, d = k_all.shape
    bs = config.block_size
    n_pages = s_pad // bs

    def paged(x):
        q, scale = _to_storage(x, config)
        # (P*bs, hk, dk) -> (P, hk, bs, dk)
        q = q.reshape(n_pages, bs, *q.shape[-2:]).transpose(0, 2, 1, 3)
        if scale is not None:
            scale = scale.reshape(n_pages, bs, h).transpose(0, 2, 1)
        return q, scale

    kq, ks = paged(k_all)
    if config.latent:
        return cache.with_layer(layer, cache.k[layer].at[blocks].set(kq),
                                None)
    vq, vs = paged(v_all)
    kc, vc, kc_scale, vc_scale = cache.layer(layer)
    kc = kc.at[blocks].set(kq)
    vc = vc.at[blocks].set(vq)
    if config.quantized:
        kc_scale = kc_scale.at[blocks].set(ks)
        vc_scale = vc_scale.at[blocks].set(vs)
    return cache.with_layer(layer, kc, vc, kc_scale, vc_scale)


def prefix_chain_keys(prompt: Sequence[int], block_size: int):
    """(full-block chain keys, partial-tail key or None) for a prompt.
    Key ``i`` commits to tokens ``[0, (i+1)*bs)`` — a chain, so
    matching key ``i`` implies matching every earlier block too.  The
    ONE hashing convention for the whole serving stack: the manager's
    shared-prefix index, the fleet router's sticky-warm probe
    (:meth:`KVCacheManager.prefix_keys` against a prompt's keys), and
    the disaggregated KV handoff's registration all speak it — so a
    key computed on one replica addresses the same content on any
    other."""
    bs = int(block_size)
    h = hashlib.blake2b(b"apex-prefix", digest_size=16)
    keys: List[bytes] = []
    full = len(prompt) // bs
    for i in range(full):
        h.update(np.asarray(prompt[i * bs:(i + 1) * bs],
                            np.int64).tobytes())
        keys.append(h.digest())
    pkey = None
    tail = prompt[full * bs:]
    if len(tail):
        hp = h.copy()
        hp.update(b"partial")
        hp.update(np.asarray(tail, np.int64).tobytes())
        pkey = hp.digest()
    return keys, pkey


class PrefixMatch(NamedTuple):
    """What :meth:`KVCacheManager.match_prefix` found for a prompt.

    ``blocks`` are the shared page ids to map (in page order),
    ``tokens`` the prompt positions their cached k/v covers (the
    prefill-skipped span — always ``<= len(prompt) - 1``, so at least
    one tail token runs through the model to produce the first
    generated token), ``cow`` whether the LAST mapped block must be
    copied-on-write before the tail prefill (the tail's first write
    lands inside it — the full-prompt warm-hit case)."""

    blocks: Tuple[int, ...]
    tokens: int
    cow: bool

    @property
    def warm(self) -> bool:
        return bool(self.blocks)


_NO_MATCH = PrefixMatch(blocks=(), tokens=0, cow=False)


class KVCacheManager:
    """Host-side block pool + per-request block tables, with optional
    copy-on-write prompt-prefix sharing.

    Free blocks form a LIFO stack: an evict-then-readmit cycle hands
    the same ids back (the tests' bitwise block-reuse proof), and hot
    blocks stay hot.  All methods are O(pages touched).

    **Prefix sharing** (``prefix_sharing=True``): full prompt blocks
    are chain-content-hashed into ``_index`` (hash of block ``i``
    commits to every token before it, so a hit is a hit on the whole
    prefix, not one block's bytes), plus one entry for the prompt's
    final partial block.  A shared block carries a refcount = number
    of request tables mapping it; it is **read-only** while mapped —
    a write into it (the owner's first decode append into its partial
    prompt block, or a warm full-prompt hit's tail re-prefill) must go
    through :meth:`cow_for_append` / :meth:`make_private`, which swap
    in a fresh private block and hand the caller the (src, dst) pair
    to device-copy.  Eviction decrements refcounts; a block reaching
    zero moves to an **idle LRU** (still cached, OFF the free list) so
    a later identical prompt still hits warm — idle blocks are
    reclaimed (unregistered) only when an allocation finds the free
    list empty.  ``can_admit`` counts idle blocks as available and a
    warm request's need as only its unshared tail.

    **The pooled kind** (``config.window``): a request's pages stop
    growing with its length.  It holds TWO lists: the pages of its
    current window (``blocks``: position ``t`` lies on page ``(t mod
    window) // bs`` of them) and its summary pages (``summary_blocks``:
    the pooled row of page ``c`` of the sequence is row ``c mod bs`` of
    summary page ``c // bs``, taken when position ``c * bs + bs - 1``
    is appended).  When the length reaches a multiple of ``window`` the
    window has closed: once the step that wrote its last position has
    been handed to the device the caller gives its pages back
    (:meth:`close_window`; not before, or another row of the same step
    could be handed a page this one still reads).  The block table of a
    step is the summary pages of the CLOSED windows, all of them whole,
    and then the window's own pages, so its live rows are contiguous
    (:meth:`block_table`, :meth:`num_pages`).  Prefix sharing refuses
    this kind: a shared prefix would be summary rows and window pages
    of another request's windows."""

    def __init__(self, config: KVCacheConfig, *,
                 prefix_sharing: bool = False):
        if config.pooled and prefix_sharing:
            raise ValueError(
                "prefix sharing and copy-on-write do not serve the pooled "
                "cache (KVCacheConfig.window) yet: a request's pages are "
                "its own window's and its own summaries")
        self.config = config
        # stack: pop() from the end; ids descend so the FIRST blocks
        # handed out are 1, 2, 3, ... (stable, test-friendly)
        self._free: List[int] = list(range(config.num_blocks - 1, 0,
                                           -1))
        self._tables: Dict[object, List[int]] = {}
        self._summaries: Dict[object, List[int]] = {}   # the pooled kind
        self._lens: Dict[object, int] = {}
        self.prefix_sharing = bool(prefix_sharing)
        self._index: Dict[bytes, int] = {}       # chain key -> block
        self._block_key: Dict[int, bytes] = {}   # reverse
        self._refs: Dict[int, int] = {}          # active mappings
        self._idle: "OrderedDict[int, None]" = OrderedDict()
        self._shared_of: Dict[object, set] = {}
        # lifetime stats (the ServeSummary / gauge feed; the engine
        # owns the token-level warm-hit accounting)
        self.prefix_hits = 0
        self.cow_copies = 0
        self.shared_blocks_hw = 0
        self.used_blocks_hw = 0          # the pool's high-water mark

    # --- capacity -----------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def idle_blocks(self) -> int:
        """Shared blocks no live request maps (cached, reclaimable)."""
        return len(self._idle)

    @property
    def available_blocks(self) -> int:
        """What an allocation can actually draw on: the free list
        plus idle shared blocks (reclaimed LRU-first on demand)."""
        return len(self._free) + len(self._idle)

    @property
    def shared_blocks(self) -> int:
        return len(self._block_key)

    @property
    def used_blocks(self) -> int:
        return self.config.usable_blocks - len(self._free)

    def can_admit(self, prompt_len: int, max_new_tokens: int, *,
                  reserved_blocks: int = 0,
                  prefix: Optional[PrefixMatch] = None) -> bool:
        """Reservation admission: the request's WHOLE worst case
        (``prompt_len + max_new_tokens``) must fit the pool right
        now, net of ``reserved_blocks`` the pool already owes
        in-flight requests (their own worst cases minus the pages
        they hold) — so a later :meth:`append` can never exhaust the
        pool mid-decode.  Admitting on anything weaker (e.g. prompt
        plus one token of headroom) re-opens exactly that crash.

        A warm ``prefix`` (from :meth:`match_prefix`) shrinks the
        bill: mapped shared pages come from the index, not the pool,
        so only the unshared tail (plus one replacement block when
        ``prefix.cow`` says the last mapped page will be
        copied-on-write) counts against the free list — warm prefixes
        admit more load, not just faster.  Matched blocks currently
        parked idle are excluded from the available count (mapping
        them consumes their idle slot, not a free block)."""
        s = len(prefix.blocks) if prefix is not None else 0
        cow = prefix.cow if prefix is not None else False
        idle_matched = sum(1 for b in (prefix.blocks if prefix
                                       else ()) if b in self._idle)
        need = self.config.blocks_for(prompt_len + max_new_tokens) \
            - s + (1 if cow else 0)
        return need <= self.available_blocks - idle_matched \
            - reserved_blocks

    # --- prefix index -------------------------------------------------

    def _chain_keys(self, prompt: Sequence[int]):
        """(full-block chain keys, partial-tail key or None) — see
        :func:`prefix_chain_keys`."""
        return prefix_chain_keys(prompt, self.config.block_size)

    def match_prefix(self, prompt: Sequence[int]) -> PrefixMatch:
        """Longest warm prefix of ``prompt`` in the shared index.
        Never covers the final token (the tail prefill must emit the
        first generated token); a match reaching the whole prompt maps
        every page and flags the last one for copy-on-write instead."""
        if not self.prefix_sharing or len(prompt) < 2:
            return _NO_MATCH
        keys, pkey = self._chain_keys(prompt)
        blocks: List[int] = []
        for key in keys:
            blk = self._index.get(key)
            if blk is None:
                break
            blocks.append(blk)
        tokens = len(blocks) * self.config.block_size
        cow = False
        if len(blocks) == len(keys) and pkey is not None:
            blk = self._index.get(pkey)
            if blk is not None:
                blocks.append(blk)
                tokens = len(prompt)
        if not blocks:
            return _NO_MATCH
        if tokens >= len(prompt):
            # full-prompt hit: the tail is the final token, whose page
            # is the last mapped block — copy-on-write before writing
            tokens = len(prompt) - 1
            cow = True
        return PrefixMatch(blocks=tuple(blocks), tokens=tokens,
                           cow=cow)

    def register_prefix(self, rid, prompt: Sequence[int]) -> int:
        """Index ``rid``'s freshly prefilled prompt pages as shared:
        every full block plus the final partial block, keyed by the
        content chain.  Pages already mapped from the index stay as
        they are; content another block already owns is not
        re-registered (two identical cold admissions race — first
        writer wins, the second's pages stay private).  Returns the
        number of newly registered blocks.  Call only after the
        prompt's k/v is fully written (a concurrent warm admission
        must never map unwritten pages)."""
        if not self.prefix_sharing:
            return 0
        keys, pkey = self._chain_keys(prompt)
        table = self._tables[rid]
        shared = self._shared_of.setdefault(rid, set())
        new = 0
        entries = list(enumerate(keys))
        if pkey is not None:
            entries.append((len(keys), pkey))
        for page, key in entries:
            blk = table[page]
            owner = self._index.get(key)
            if owner is not None:
                continue                  # mapped warm, or a duplicate
            if blk in self._block_key:
                continue                  # block already shared as
            self._index[key] = blk        # different content (cannot
            self._block_key[blk] = key    # happen via alloc, belt+
            self._refs[blk] = 1           # braces)
            shared.add(blk)
            new += 1
        self.shared_blocks_hw = max(self.shared_blocks_hw,
                                    len(self._block_key))
        return new

    def prefix_keys(self):
        """The shared index's chain keys (bytes digests) as a LIVE
        read-only set view — the cheap warm-prefix probe surface
        :meth:`~apex_tpu.serving.engine.ServingEngine.router_snapshot`
        exports.  A router hashes a candidate prompt ONCE
        (:func:`prefix_chain_keys`) and membership-probes each
        replica's view (O(1) per key, no index copy per poll — the
        index can hold thousands of chains on a warm replica); it
        must not mutate or retain the view across engine mutations."""
        return self._index.keys()

    def resident_prefix(self, prompt: Sequence[int]
                        ) -> Optional[List[int]]:
        """The block list holding ``prompt``'s ENTIRE k/v in this
        pool's shared index (every full block plus the partial tail),
        in page order — the export unit of the disaggregated KV
        handoff — or None when any page is missing.  Unlike
        :meth:`match_prefix` this includes the final token's page
        unconditionally: an exporter ships content, it does not admit
        a request."""
        if not self.prefix_sharing or not len(prompt):
            return None
        keys, pkey = self._chain_keys(prompt)
        blocks: List[int] = []
        for key in keys + ([pkey] if pkey is not None else []):
            blk = self._index.get(key)
            if blk is None:
                return None
            blocks.append(blk)
        return blocks

    def register_external(self, prompt: Sequence[int],
                          payload_pages: int) -> Optional[List[int]]:
        """Claim pool blocks for an IMPORTED prompt's k/v (the decode
        side of the disaggregated handoff) and index them as shared
        with zero live mappings — parked in the idle LRU, exactly the
        state a finished local request's prompt pages land in — so the
        next admission of this prompt maps them warm.  Returns the
        claimed block ids (in page order, the scatter destination), or
        None when the prompt (or a block-content collision) is already
        resident — the importer then skips the device scatter
        entirely.  Raises :class:`CachePoolExhausted` when the pool
        cannot cover ``payload_pages`` blocks."""
        if not self.prefix_sharing:
            raise ValueError(
                "register_external needs prefix_sharing=True — "
                "imported pages are addressed through the shared "
                "index (the warm-admission machinery)")
        keys, pkey = self._chain_keys(prompt)
        entries = keys + ([pkey] if pkey is not None else [])
        if len(entries) != int(payload_pages):
            raise ValueError(
                f"payload covers {payload_pages} page(s) but the "
                f"prompt chains into {len(entries)} — block_size "
                f"mismatch between the replicas?")
        if all(k in self._index for k in entries):
            return None                       # already resident
        if payload_pages > self.available_blocks:
            raise CachePoolExhausted(
                f"import needs {payload_pages} block(s), pool has "
                f"{self.available_blocks} available")
        blocks: List[int] = []
        fresh: List[int] = []
        # resident owners this import reuses leave the idle LRU for
        # the duration of the claim loop: _take_block reclaims LRU
        # idle blocks when the free list is dry, and stealing a page
        # that is already on this import's block list would both
        # unregister its chain entry and alias two payload pages into
        # one block (one silently lost)
        shelved: List[int] = []
        for key in entries:
            owner = self._index.get(key)
            if owner is not None and owner in self._idle:
                del self._idle[owner]
                shelved.append(owner)
        try:
            for key in entries:
                owner = self._index.get(key)
                if owner is not None:
                    # chain prefix already cached here: reuse the
                    # resident page (the scatter rewrites it with
                    # identical bytes — content-addressed no-op)
                    blocks.append(owner)
                    continue
                blk = self._take_block("import: pool drained "
                                       "mid-claim")
                self._index[key] = blk
                self._block_key[blk] = key
                self._refs[blk] = 0
                blocks.append(blk)
                fresh.append(blk)
        finally:
            for blk in shelved:
                self._idle[blk] = None        # back in the LRU
        for blk in fresh:
            # parked idle only AFTER every claim, same hazard as above
            self._idle[blk] = None            # cached, reclaimable
        self.shared_blocks_hw = max(self.shared_blocks_hw,
                                    len(self._block_key))
        return blocks

    def _map_shared(self, rid, blk: int) -> None:
        self._refs[blk] = self._refs.get(blk, 0) + 1
        self._idle.pop(blk, None)
        self._shared_of.setdefault(rid, set()).add(blk)

    def _unmap_shared(self, blk: int) -> None:
        self._refs[blk] -= 1
        if self._refs[blk] == 0:
            # cached but unmapped: off the free list, reclaimable LRU
            self._idle[blk] = None

    def _take_block(self, why: str) -> int:
        """One block off the free list, reclaiming the LRU idle shared
        block (unregistering its prefix entry) when the list is dry."""
        if self._free:
            blk = self._free.pop()
            self.used_blocks_hw = max(self.used_blocks_hw, self.used_blocks)
            return blk
        if self._idle:
            blk, _ = self._idle.popitem(last=False)
            key = self._block_key.pop(blk)
            del self._index[key]
            del self._refs[blk]
            return blk
        raise CachePoolExhausted(why)

    def is_shared(self, rid, block: int) -> bool:
        """Whether ``block`` is a read-only shared mapping in
        ``rid``'s table (a write must CoW it first)."""
        return block in self._shared_of.get(rid, ())

    # --- lifecycle ----------------------------------------------------

    def alloc(self, rid, length: int, *,
              shared_blocks: Sequence[int] = ()) -> List[int]:
        """Claim blocks covering ``length`` tokens for a new request.
        ``shared_blocks`` (from :meth:`match_prefix`) are mapped
        read-only as the table's leading pages — refcounted, never
        drawn from the pool — and only the tail is allocated."""
        if rid in self._tables:
            raise ValueError(f"request {rid!r} already has blocks")
        if length < 1:
            raise ValueError("length must be >= 1")
        if self.config.pooled:
            if shared_blocks or length > self.config.window:
                raise ValueError(
                    f"request {rid!r}: a pooled cache allocates one "
                    f"window ({self.config.window} positions) at most and "
                    f"maps no shared page; grow_to() takes the next")
            self._tables[rid], self._summaries[rid] = [], []
            self._lens[rid] = 0
            try:
                self.grow_to(rid, length)
            except CachePoolExhausted:
                self.free(rid)
                raise
            return list(self._tables[rid])
        need = self.config.blocks_for(length) - len(shared_blocks)
        if need < 0:
            raise ValueError(
                f"request {rid!r}: {len(shared_blocks)} shared pages "
                f"exceed the {self.config.blocks_for(length)} pages "
                f"length {length} occupies")
        idle_matched = sum(1 for b in shared_blocks
                           if b in self._idle)
        if need > self.available_blocks - idle_matched:
            raise CachePoolExhausted(
                f"request {rid!r} needs {need} block(s) for length "
                f"{length}, pool has {self.available_blocks} "
                f"available of {self.config.usable_blocks}")
        blocks = list(shared_blocks)
        for blk in shared_blocks:
            self._map_shared(rid, blk)
        blocks.extend(self._take_block(
            f"request {rid!r}: pool drained mid-alloc")
            for _ in range(need))
        self._tables[rid] = blocks
        self._lens[rid] = int(length)
        if shared_blocks:
            self.prefix_hits += 1
        return list(blocks)

    def cow_for_append(self, rid):
        """Copy-on-write guard for the next :meth:`append`: when the
        slot the next token lands in sits inside a shared (read-only)
        page — the owner's first append into its registered partial
        prompt block — swap in a fresh private block and return
        ``(src, dst)`` for the caller to device-copy.  Returns None
        when the next write is already private."""
        pos = self._lens[rid]
        page = pos // self.config.block_size
        if page >= len(self._tables[rid]):
            return None                       # append opens a new page
        return self.make_private(rid, page)

    def make_private(self, rid, page: int):
        """CoW page ``page`` of ``rid``'s table if it is a shared
        mapping: allocate a private replacement, swap the table entry,
        release the shared ref.  Returns ``(src_block, dst_block)``
        to device-copy, or None if the page is already private."""
        blocks = self._tables[rid]
        src = blocks[page]
        if not self.is_shared(rid, src):
            return None
        dst = self._take_block(
            f"request {rid!r}: no block for the copy-on-write of "
            f"shared page {page}")
        blocks[page] = dst
        self._shared_of[rid].discard(src)
        self._unmap_shared(src)
        self.cow_copies += 1
        return src, dst

    def pending_cow_blocks(self, rid) -> int:
        """1 when ``rid``'s next append will CoW a shared page (the
        reservation math must hold that block back), else 0."""
        pos = self._lens[rid]
        page = pos // self.config.block_size
        blocks = self._tables[rid]
        if page < len(blocks) and self.is_shared(rid, blocks[page]):
            return 1
        return 0

    def append(self, rid):
        """Grow ``rid`` by one token, allocating a fresh block when
        the token starts a new page.  Returns ``(block_id, offset)``
        — the page slot the new token's k/v must be written to (its
        position is the pre-append ``seq_len``).  Writing into a
        shared page is a contract violation: call
        :meth:`cow_for_append` first (the engine does, copying the
        block on device)."""
        blocks = self._tables[rid]
        pos = self._lens[rid]
        if self.config.pooled:
            self.grow_to(rid, pos + 1)
            return blocks[-1], pos % self.config.block_size
        page, off = divmod(pos, self.config.block_size)
        if page == len(blocks):
            blocks.append(self._take_block(
                f"request {rid!r} crossed a block edge at length "
                f"{pos + 1} with the pool empty — admission "
                f"control must keep headroom (can_admit)"))
        elif self.is_shared(rid, blocks[page]):
            raise RuntimeError(
                f"request {rid!r}: append would write into shared "
                f"page {page} (block {blocks[page]}) — the caller "
                f"must cow_for_append() first")
        self._lens[rid] = pos + 1
        return blocks[page], off

    def grow_to(self, rid, length: int) -> None:
        """Raise ``rid``'s length to ``length`` positions, claiming the
        pages they land on: what a prefill chunk does before it is
        written (a request whose :meth:`alloc` covered its whole prompt
        has nothing to claim).  The pooled kind grows inside ONE window
        -- a closed window is given back first (:meth:`close_window`) --
        and claims a summary page with the first page that pools to
        it."""
        cfg, pos = self.config, self._lens[rid]
        if length <= pos:
            return
        if not cfg.pooled:
            while self._lens[rid] < length:
                self.append(rid)
            return
        blocks, summaries = self._tables[rid], self._summaries[rid]
        if pos // cfg.window != (length - 1) // cfg.window \
                or (pos % cfg.window == 0 and blocks):
            raise RuntimeError(
                f"request {rid!r}: positions {pos}..{length - 1} do not "
                f"lie in one open window of {cfg.window} (close_window() "
                f"gives a closed one back first)")
        bs = cfg.block_size
        want = -(-((length - 1) % cfg.window + 1) // bs) - len(blocks)
        want_sum = -(-(length // bs) // bs) - len(summaries)
        if want + want_sum > self.available_blocks:
            raise CachePoolExhausted(
                f"request {rid!r} needs {want + want_sum} block(s) to "
                f"reach length {length}, pool has {self.available_blocks} "
                f"— admission control must keep headroom (can_admit)")
        why = f"request {rid!r}: pool drained growing to {length}"
        blocks.extend(self._take_block(why) for _ in range(want))
        summaries.extend(self._take_block(why) for _ in range(want_sum))
        self._lens[rid] = int(length)

    def pool_slot(self, rid) -> Tuple[int, int]:
        """Where the pooled row of the page that ``rid``'s newest
        position completed goes, as ``(summary block, row)``; the dump
        page when that position completed none."""
        bs = self.config.block_size
        chunk, rest = divmod(self._lens[rid], bs)
        if rest or not chunk:
            return DUMP_BLOCK, 0
        return self._summaries[rid][(chunk - 1) // bs], (chunk - 1) % bs

    def close_window(self, rid) -> List[int]:
        """Give back the pages of ``rid``'s window if its last position
        has been appended (the length is a multiple of ``window``):
        what is kept of a closed window is its pooled rows.  Call once
        the step that wrote that position has been handed to the
        device.  Returns the freed block ids."""
        blocks = self._tables[rid]
        if not self.config.pooled or not blocks \
                or self._lens[rid] % self.config.window:
            return []
        freed = list(blocks)
        self._free.extend(reversed(blocks))
        blocks.clear()
        return freed

    def truncate(self, rid, new_len: int) -> List[int]:
        """Roll ``rid``'s write cursor back to ``new_len`` tokens
        (speculative-decode rejection), returning pages past the new
        end to the pool.  Only ever sheds private blocks the same
        tick's appends claimed — a rollback never reaches below the
        prompt, so shared pages are untouchable by construction."""
        if not 1 <= new_len <= self._lens[rid]:
            raise ValueError(
                f"request {rid!r}: truncate to {new_len} outside "
                f"[1, {self._lens[rid]}]")
        if self.config.pooled:
            raise ValueError(
                "the pooled cache has no rollback: a pooled row cannot be "
                "taken back (speculative decoding does not serve it yet)")
        blocks = self._tables[rid]
        keep = self.config.blocks_for(new_len)
        freed: List[int] = []
        while len(blocks) > keep:
            blk = blocks.pop()
            if blk in self._block_key:
                raise RuntimeError(
                    f"request {rid!r}: truncate would free shared "
                    f"block {blk} — rollback crossed the prompt")
            self._free.append(blk)
            freed.append(blk)
        self._lens[rid] = int(new_len)
        return freed

    def free(self, rid) -> List[int]:
        """Return ``rid``'s blocks to the pool (LIFO, reverse order so
        a readmit walks them back out first-block-first).  Shared
        mappings are unref'd instead — a block another table still
        maps stays live, and one reaching zero refs parks in the idle
        LRU (still indexed, warm for the next identical prompt)."""
        blocks = self._tables.pop(rid) + self._summaries.pop(rid, [])
        del self._lens[rid]
        shared = self._shared_of.pop(rid, set())
        for blk in reversed(blocks):
            if blk in shared:
                self._unmap_shared(blk)
            else:
                self._free.append(blk)
        return blocks

    # --- views --------------------------------------------------------

    def requests(self):
        return list(self._tables)

    def seq_len(self, rid) -> int:
        return self._lens[rid]

    def blocks(self, rid) -> List[int]:
        """``rid``'s pages in position order (the pooled kind: those of
        its current window)."""
        return list(self._tables[rid])

    def summary_blocks(self, rid) -> List[int]:
        """The pooled kind: ``rid``'s summary pages, in order."""
        return list(self._summaries[rid])

    def held_blocks(self, rid) -> int:
        """Blocks ``rid`` holds now, of every list."""
        return len(self._tables[rid]) + len(self._summaries.get(rid, ()))

    def _closed_pages(self, rid) -> int:
        """The pooled kind: the summary pages of ``rid``'s closed
        windows, each whole; they lead its block table."""
        if not self.config.pooled:
            return 0
        return max(self._lens[rid] - 1, 0) // self.config.window \
            * self.config.window_summary_pages

    def _table(self, rid) -> List[int]:
        """The pages a step reads for ``rid``, in the order its block
        table lists them: every page; the pooled kind the summary pages
        of the closed windows and then the window's own."""
        if not self.config.pooled:
            return self._tables[rid]
        return self._summaries[rid][:self._closed_pages(rid)] \
            + self._tables[rid]

    def block_table(self, rid, max_pages: int) -> np.ndarray:
        """(max_pages,) int32, padded with the dump block."""
        blocks = self._table(rid)
        if len(blocks) > max_pages:
            raise ValueError(
                f"request {rid!r} owns {len(blocks)} pages > bucket "
                f"max_pages {max_pages} — the ladder pick is wrong")
        bt = np.full(max_pages, DUMP_BLOCK, np.int32)
        bt[:len(blocks)] = blocks
        return bt

    def num_pages(self, rid) -> int:
        """The columns of ``rid``'s block table that name a page."""
        return self._closed_pages(rid) + len(self._tables[rid])
