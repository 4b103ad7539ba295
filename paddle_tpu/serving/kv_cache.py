"""Paged per-slot KV cache with prefix sharing for autoregressive decode
(serving/decode.py).

Layout (the vLLM PagedAttention idea, TPU-native): all keys/values for
every serving slot live in TWO device arrays of fixed-size pages (ONE
where a latent or a joint cache keeps a position in one row, below)

    k_pages : [cache_layers, num_pages, page_size, heads * head_dim]
    v_pages : [cache_layers, num_pages, page_size, heads * v_head_dim]

(a position's heads folded into ONE lane-dense row; see "Device layout"
below; V's heads may be narrower than K's; ``cache_layers`` is the
model's count of layers of K and V a token leaves behind: one a weight
layer that has keys, or, for a stack its tokens pass through several
times on the same weights, one a pass a layer) and each slot owns an
ordered list of page ids (its *page table*).  A
slot's logical sequence position ``t`` maps to page ``table[t // page]``
offset ``t % page``.  Pages are allocated from a host-side free list at
admission and returned when their REFCOUNT drops to zero — a finished
slot releases its references immediately instead of padding to the
longest request in a batch.

Page 0 is the TRASH page: it is never allocated, dead slots' per-step
writes land there, and an empty page-table entry points at it.  Reads
are always masked by the slot's live length, so trash contents are
never observable.

**Prefix sharing** (this file's tentpole): at millions of users most
prompts open with the same system/template prefix, so recomputing and
re-storing its K/V per request wastes both HBM and prefill compute.
When a request finishes, its pages are registered in a host-side
``PrefixIndex`` — an exact token-content trie keyed by
``(parent_page_id, page_token_tuple)``, collision-free by construction
(no hashing shortcut can serve a wrong byte).  Admission walks the trie
over the new prompt: every matched page is SHARED into the slot's page
table with a refcount bump instead of being allocated and prefilled.
Sharing rules that keep the device arrays coherent:

- A registered page is immutable (the index itself holds one
  reference).  A slot may only write a page it solely owns
  (``refcount == 1`` and unregistered).
- The trie's final entry may be a *partial* page (a prompt tail shorter
  than one page).  A consumer that matches it borrows the page and
  must **copy-on-write** before its first divergent token lands there:
  ``plan_cow`` swaps the slot's reserved spare page into the table and
  returns the ``(src, dst)`` device copy the engine must perform before
  its next write dispatch.
- Worst-case reservation stays shared-aware and exhaustion-proof: a
  claim allocates ``total_pages - shared_full_pages`` fresh pages —
  when a partial page is borrowed, one of those fresh pages is held
  back as the CoW spare, so the mid-decode copy can NEVER fail on an
  empty pool (a decode step still never dies on cache exhaustion).
- Under pool pressure, admission evicts least-recently-hit CHILDLESS
  index entries whose pages only the index references (bottom-up, so a
  reused page id can never be mistaken for a live trie parent).

The device arrays themselves are registered in a ``framework.Scope``
and threaded through ``Executor.run_persistent`` with donation — the
cache never round-trips to host between steps.  The speculative-decode
draft model's page pools (serving/decode.py) are indexed by the SAME
page ids, so sharing, reservation, and CoW cover them for free (the
engine's CoW copy spans every pool).

**Device layout** (the tile rule).  The chip lays an array out in
(8, 128) tiles over its two trailing dimensions, and layout assignment
follows the array's SHAPE: a pool whose trailing dimensions fill those
tiles exactly (``heads * head_dim`` a multiple of 128, ``page_size`` a
multiple of 8), written at the third-from-last axis (the offset), is
row-major from allocation to the end of the process — every serving
program takes it and returns it in that one layout, and a token's K/V
lands by an in-place scatter.  A trailing ``[..., heads, 64]`` instead
was re-laid-out on entry to and exit from every program (four
whole-pool copies a decode step at GPT-2 widths) and padded 2x in
lanes.  So a position is stored as ONE row of ``heads * head_dim``
lanes: ``[R, H, D] -> [R, H*D]`` is a free row-major reshape on write,
and readers reshape back (`ops/pallas_decode_attention.py` reads heads
straight out of lanes).  Axes 0-2 (layer, page id, offset) are what all
host bookkeeping, export/install and copy-on-write index; they never
see the fold.  A shape that cannot be lane-dense still works, only
padded: ``CacheConfig.lane_dense`` says which.

**Two kinds of state, one allocator** (``RecurrentSpec``).  A model
whose layers are not all softmax attention keeps, for the others, a
FIXED-SIZE state a request instead of keys: a linear-attention layer's
``[heads, d_k, d_v]`` matrix and the few positions its short convolution
looks back on.  The pools then hold the attention layers only (``layer``
above counts those), and each recurrent layer's state lives in slabs
indexed by SLOT, ``[num_slots, *shape]`` one array a layer and name,
beside the pools in the same scope and threaded through the same donated
programs: the slot is the allocation, claimed and released with the
slot's pages by the one ``claim``/``release``.  A slab row needs no
clearing: the whole-prompt prefill that opens a request starts from zero
state and overwrites the row with the state its last token left.  Such
a cache has NO prefix index: a page of keys says nothing about the state
three quarters of the layers reached after the same tokens, so every
request is admitted fresh (``prefix_bypassed`` says the index was asked
for and left out; the engine counts the admissions).

**Geometry by layer kind, two lifetimes** (``WindowSpec``).  A layer
that attends a sliding window needs a slot's last ``window`` positions
and no others, however long the request grows, and may have other head
counts than the layers that attend everything.  Its K/V live in a second
pair of pools of their own geometry

    window k/v : [window_layers, num_slots * ring + 1, page_size, lanes]

in which a slot owns a RING of ``ring = ceil(window / page_size) + 1``
pages: position ``t`` lands in ring entry ``(t // page_size) % ring`` at
offset ``t % page_size``, overwriting the page that slid out of the
window (the ``+ 1``: a window that starts mid-page still has its oldest
positions while the newest page fills).  The slot IS the allocation, as
for recurrent state: slot ``s`` owns pages ``1 + s * ring ...``, claimed
and released with the slot by the one ``claim``/``release``, page 0 is
this pool's own trash page, nothing is uploaded for it (``ring_table``
is a constant) and its bytes do not depend on ``max_seq_len``.  The
pools above then hold the layers that attend every position only.  A
cache with window layers has NO prefix index either (a shared page of a
global layer says nothing of what the ring held at that position), and
no page of it can be exported: ``prefix_bypassed`` as for recurrent
layers.

**One row for keys and values** (``CacheConfig(latent=True)``).  A
model with latent attention caches ONE row a position a layer, shared by
all its query heads: the scores read the whole row, the values are its
first ``v_head_dim`` lanes.  Such a cache has the K pool ALONE, behind
the same free list, tables and copy-on-write (``state_var_names`` is one
name); there is no V pool to keep in step.  The row is stored at whole
lane tiles (``row_lanes``: 576 lanes of a latent and its rotary key take
640, the rest zeros that a zero-padded query meets), said by the shape
and by ``latent_bytes`` rather than left to the chip's own padding.  No
prefix index (``prefix_bypassed``), no int8 form, no export: the
programs that would read such rows R at a time (a prefix hit's suffix,
a chunk, a speculative window) are not built for them.

**One row for keys AND values of one head** (``CacheConfig.joint``).
A cache whose layers have ONE K/V head, unquantized, with keys and values
each a whole number of lane tiles wide (``head_dim % 128 == 0`` and
``v_head_dim % 128 == 0``: AI21-Jamba2-3B's 128 + 128) keeps a position's
K and V side by side in one row of ``head_dim + v_head_dim`` lanes, in
the K pool ALONE.  The rule is read from the shape: no flag, no model's
name; a toy width, a second head, int8 pages and a latent cache keep
what they had, and the bytes are the two pools' (``cache_bytes`` does
not change).  Why: such a page is 16 rows of 128 bfloat16 lanes = 4 KB
a pool, the paged kernel starts one copy a page a pool, and at that size
a call is bound by the COUNT of its copies, not their bytes (measured on
the v5e at 256 slots of 0.3-3.6k positions: 64 copies a block of 512
positions issue in 3.7x the time the rows' bytes take; one 8 KB copy a
page halves it, `ops/pallas_decode_attention.py`'s table by
``_STACKED_BLOCK``).  The writers lay ``k`` and ``v`` of a row side by
side and land them with ONE scatter a layer; the kernel and the plain
path read the values from the lanes after the keys
(``value_offset=head_dim``), bit for bit what two pools give.  Everything
indexed by (layer, page id, offset) sees one array where it saw two:
the free list, tables, refcounts, the prefix index, copy-on-write,
``export_pages`` / ``install_pages`` (both engines derive the same rule
from the same shape, and the payload's names and shapes are checked),
``debug_check``.  Nothing refuses a joint pool: unlike the latent row it
holds what two pools hold, a head's K and V of every position, so chunks,
a prefix hit's suffix and a verify window read it R rows at a time as
they read two.  A draft model's pools are always two (they follow the
draft's shape through their own names).

**A third array a position** (``IndexSpec``).  A model whose attention
reads only the positions a learned INDEXER picks caches, beside K and V,
one small index key a position a layer; a query scores every live
position's key and attends the ``topk`` best.  The keys live in a THIRD
pool behind the SAME page ids

    index_pages : [cache_layers, num_pages, page_size, index row lanes]

so one page table, one free list and one ``claim``/``release`` cover all
three: a page's index rows are claimed, released and recycled with its
K/V rows, and nothing is cleared on release.  That is sound because the
readers mask by the slot's LENGTH before they select: a recycled page's
stale rows lie at offsets the new owner has not written yet, which are
positions past its length.  A row is stored at whole lane tiles (64
lanes take 128, the rest zeros that a zero-padded query meets), said by
the shape and by ``index_bytes`` rather than left to the chip's padding.
No prefix index (``prefix_bypassed``: a shared page would need its index
rows shared and the suffix's rows selected R at a time), no int8 form,
no export.

**Quantized storage** (``FLAGS_decode_kv_quant``): pages are stored
int8 (same folded rows) beside parallel scale pools ``[layers, pages,
page_size, heads]``
(one float32 scale per head per position-in-page; see
:class:`CacheConfig` for why the scale granularity is the page's
positions rather than one scalar per page).  Writes quantize in the
step that produces the K/V (``write_token_layer`` /
``write_prompt_layer``); both attention paths dequantize inline
(``ops/pallas_decode_attention.py``).  Bytes per page roughly halve vs
bf16, and since the admission reservation is page-count-based, a pool
sized to a fixed byte budget admits ~2x the concurrent requests.
Freed pages' scale planes reset to ``SCALE_EPS`` (batched, flushed at
release/claim) so ``debug_check`` can audit scale-pool/page-pool
agreement.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..monitor import stat_add
from ..observe.histogram import stat_time
from ..ops.quant_ops import SCALE_EPS

K_PAGES_VAR = "__decode_k_pages__"
V_PAGES_VAR = "__decode_v_pages__"
K_SCALES_VAR = "__decode_k_scales__"
V_SCALES_VAR = "__decode_v_scales__"
WINDOW_K_VAR = "__decode_window_k_pages__"
WINDOW_V_VAR = "__decode_window_v_pages__"
INDEX_PAGES_VAR = "__decode_index_pages__"

KV_QMAX = 127.0  # symmetric int8 grid for quantized pages


class CacheExhaustedError(RuntimeError):
    """The page pool cannot cover a request's worst-case reservation."""


class KVPageExport:
    """A self-describing export of one slot's leading KV pages — the
    disaggregated-serving migration payload (serving/disagg.py).

    ``arrays`` maps every pool var name from ``state_var_names()``
    (data pages AND, when quantized, the scale planes) to a
    ``[layers, n_pages, ...]`` slice gathered out of the source pool.
    The slices are fresh buffers (a jax gather never aliases the
    donated pool), so a payload stays valid after the source engine's
    next step; ``np.asarray`` each array for the host-bounce transport
    when source and destination do not share a backend.  ``quantized``
    and ``page_size`` let the destination reject a geometry-mismatched
    install before touching its pools."""

    __slots__ = ("n_tokens", "n_pages", "src_pages", "arrays",
                 "quantized", "page_size", "nbytes")

    def __init__(self, n_tokens: int, n_pages: int,
                 src_pages: Sequence[int], arrays: Dict[str, object],
                 quantized: bool, page_size: int):
        self.n_tokens = int(n_tokens)
        self.n_pages = int(n_pages)
        self.src_pages = list(src_pages)
        self.arrays = dict(arrays)
        self.quantized = bool(quantized)
        self.page_size = int(page_size)
        self.nbytes = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in self.arrays.values())


class CacheConfig:
    """Geometry of the paged cache (everything static / compile-time).

    A pool is ``[num_layers, num_pages, page_size, row_lanes]``
    (``num_layers``: the CACHE layers, the pools' depth, which a model
    may declare apart from its weight layers) with
    ``row_lanes = num_heads * head_dim`` (``v_row_lanes = num_heads *
    v_head_dim`` for the V pool where ``v_head_dim`` is given): one
    position's heads folded
    into one row (the module header's tile rule).  ``joint`` (derived,
    the module header): one K/V head of whole lane tiles has the K pool
    alone, ``row_lanes = head_dim + v_head_dim`` and ``v_row_lanes = 0``.  ``lane_dense`` says
    whether that row and the page fill the chip's (8, 128) tiles
    exactly, i.e. whether the pool keeps one unpadded layout through
    every program.

    ``quantized=True`` (``FLAGS_decode_kv_quant``) stores pages as int8
    with a parallel per-page scale pool: one float32 scale per head per
    position-in-page (a ``[page_size, heads]`` scale plane per page,
    living in ``k/v_scales [layers, pages, page_size, heads]``).  The
    position-granular plane — rather than one scalar per page — is what
    keeps stored bytes WRITE-ONCE: re-deriving a position (a rejected
    speculative row, a chunked-prefill replay) re-quantizes only itself,
    so page content is order-independent and speculative decode stays
    bitwise-equal to its own non-speculative quantized run.  Bytes per
    position drop from ``2*head_dim`` (bf16) to ``head_dim + 4`` —
    about half — which is exactly what ``page_bytes()`` reports, so the
    worst-case admission reservation and the PR 8 HBM accounting both
    see the shrink and a fixed pool byte budget holds ~2x the pages."""

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 num_slots: int, max_seq_len: int, page_size: int,
                 num_pages: Optional[int] = None, dtype="float32",
                 quantized: bool = False,
                 v_head_dim: Optional[int] = None,
                 latent: bool = False):
        if max_seq_len % page_size:
            raise ValueError(
                f"max_seq_len ({max_seq_len}) must be a multiple of "
                f"page_size ({page_size})")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.v_head_dim = int(head_dim if v_head_dim is None
                              else v_head_dim)
        self.num_slots = int(num_slots)
        self.max_seq_len = int(max_seq_len)
        self.page_size = int(page_size)
        self.pages_per_slot = self.max_seq_len // self.page_size
        # default pool: every slot can hold a max-length sequence, plus
        # the reserved trash page — admission then only ever blocks on
        # free SLOTS, never pages.  A smaller explicit pool exercises
        # real paging pressure (admission waits for pages).
        self.num_pages = int(num_pages) if num_pages is not None \
            else self.num_slots * self.pages_per_slot + 1
        if self.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is trash)")
        self.quantized = bool(quantized)
        # one row a position for scores AND values (the module header)
        self.latent = bool(latent)
        if self.latent and (self.quantized or self.num_heads != 1
                            or self.v_head_dim > self.head_dim):
            raise ValueError(
                "a latent page is ONE unquantized row a position whose "
                "leading lanes are the values: num_heads 1, v_head_dim "
                "<= head_dim, no kv_quant")
        # ``dtype`` stays the COMPUTE/reference dtype (what dequantized
        # values and the full-recompute oracle use); ``store_dtype`` is
        # what the page pools hold
        self.dtype = np.dtype(dtype)
        self.store_dtype = np.dtype(np.int8) if self.quantized \
            else self.dtype
        self.scale_dtype = np.dtype(np.float32)

    @property
    def joint(self) -> bool:
        """Whether a position's K and V lie side by side in ONE pool row
        (the module header): one unquantized K/V head whose keys and
        values are each whole lane tiles wide.  Read from the shape
        alone; a toy width keeps two pools."""
        return self.num_heads == 1 and not self.latent \
            and not self.quantized and self.head_dim % 128 == 0 \
            and self.v_head_dim % 128 == 0

    @property
    def row_lanes(self) -> int:
        """Width of one stored position: every head's ``head_dim``
        values side by side; a latent row up to whole lane tiles; a
        joint row the one head's keys, then its values."""
        if self.latent:
            return -(-self.head_dim // 128) * 128
        if self.joint:
            return self.head_dim + self.v_head_dim
        return self.num_heads * self.head_dim

    @property
    def v_row_lanes(self) -> int:
        """Width of one stored position of V (a latent or a joint cache
        has no V pool: its values are lanes of the K pool's row)."""
        return 0 if self.latent or self.joint \
            else self.num_heads * self.v_head_dim

    def attended_lanes(self) -> Tuple[int, int]:
        """(K lanes, V lanes) of a position as the paged kernel's block
        rule counts them (``pages_per_block``): the two pools' rows, a
        joint row's two halves, a latent row and 0."""
        if self.joint:
            return self.head_dim, self.v_head_dim
        return self.row_lanes, self.v_row_lanes

    @property
    def lane_dense(self) -> bool:
        """Whether ``(page_size, row_lanes)`` fills the chip's (8, 128)
        tiles exactly (the module header's tile rule), V's rows too."""
        return self.row_lanes % 128 == 0 and self.v_row_lanes % 128 == 0 \
            and self.page_size % 8 == 0

    def pool_shape(self, num_layers: Optional[int] = None,
                   row_lanes: Optional[int] = None) -> Tuple[int, ...]:
        """Shape of one page pool (a joint cache's ONE pool: rows of
        keys + values); the draft model's pools share the page ids and
        differ in depth and row width only."""
        return (self.num_layers if num_layers is None else num_layers,
                self.num_pages, self.page_size,
                self.row_lanes if row_lanes is None else row_lanes)

    def pages_for(self, seq_len: int) -> int:
        return max(1, math.ceil(int(seq_len) / self.page_size))

    def page_bytes(self, v: bool = False) -> int:
        """Device bytes ONE page costs in one pool (K's, or with ``v``
        V's: 0 where a latent or a joint cache has none, the K pool's
        page then holding the values too) — including its
        scale plane when quantized, so capacity math can't hide the
        scale overhead."""
        data = (self.page_size
                * (self.v_row_lanes if v else self.row_lanes)
                * self.store_dtype.itemsize)
        if self.quantized:
            data += (self.page_size * self.num_heads
                     * self.scale_dtype.itemsize)
        return data

    def per_page_pool_bytes(self) -> int:
        """Total device bytes one page costs across EVERY pool (k + v,
        or the one pool that holds both; all cache layers, scale planes
        included) — the unit a fixed byte budget is divided by to size
        ``num_pages``."""
        return self.num_layers * (self.page_bytes()
                                  + self.page_bytes(v=True))

    def cache_bytes(self) -> int:
        """Total device bytes of the page arrays (k + v, scale pools
        included when quantized; a joint cache's one pool costs what
        the two it replaces would)."""
        return self.num_pages * self.per_page_pool_bytes()


class RecurrentSpec:
    """The per-slot state of a model's layers that keep state instead
    of keys: ``num_layers`` such layers, each holding the ``arrays``
    ``{name: (shape, dtype)}`` of ONE slot (``PagedKVCache`` allocates
    ``[num_slots, *shape]`` a layer and name)."""

    def __init__(self, num_layers: int, arrays):
        self.num_layers = int(num_layers)
        self.arrays = {str(n): (tuple(int(d) for d in shape),
                                np.dtype(dtype))
                       for n, (shape, dtype) in arrays.items()}

    def var_names(self) -> Tuple[str, ...]:
        """Scope names, layer-major, a layer's arrays in ``arrays``'
        order."""
        return tuple(f"__decode_state_{name}_{i}__"
                     for i in range(self.num_layers)
                     for name in self.arrays)

    def slot_bytes(self) -> int:
        """Device bytes one slot's state costs over all layers."""
        return self.num_layers * sum(
            int(np.prod(shape)) * dtype.itemsize
            for shape, dtype in self.arrays.values())


class WindowSpec:
    """The K/V of a model's layers that attend a sliding ``window``
    (the module header: a ring of pages a slot, in pools of their own):
    ``num_layers`` such layers, ``num_heads`` K/V heads of ``head_dim``
    (K) and ``v_head_dim`` (V) lanes."""

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 v_head_dim: int, window: int):
        self.num_layers, self.num_heads = int(num_layers), int(num_heads)
        self.head_dim, self.v_head_dim = int(head_dim), int(v_head_dim)
        self.window = int(window)
        if self.window < 1:
            raise ValueError(f"window must be positive, got {window}")

    def ring_pages(self, page_size: int) -> int:
        """Pages a slot's ring holds a layer: the window's, and one more
        for the page that fills while the oldest is still attended."""
        return math.ceil(self.window / int(page_size)) + 1

    def pool_shapes(self, num_slots: int, page_size: int):
        """(K pool shape, V pool shape); page 0 is the pools' trash."""
        lead = (self.num_layers,
                int(num_slots) * self.ring_pages(page_size) + 1,
                int(page_size))
        return (lead + (self.num_heads * self.head_dim,),
                lead + (self.num_heads * self.v_head_dim,))

    def ring_table(self, num_slots: int, page_size: int) -> np.ndarray:
        """[num_slots, ring] page ids: slot s owns ``1 + s * ring ...``;
        a constant of every program, never uploaded."""
        ring = self.ring_pages(page_size)
        return (1 + np.arange(int(num_slots) * ring, dtype=np.int32)
                ).reshape(int(num_slots), ring)

    def bytes(self, num_slots: int, page_size: int, itemsize: int) -> int:
        """Device bytes of both pools: no term in ``max_seq_len``."""
        return sum(int(np.prod(shape)) * int(itemsize)
                   for shape in self.pool_shapes(num_slots, page_size))


class IndexSpec:
    """The index keys of a model whose attention layers select the
    positions they attend (the module header: a third pool behind the
    K/V pools' page ids): ``num_layers`` such layers, ``key_dim`` lanes a
    position."""

    def __init__(self, num_layers: int, key_dim: int):
        self.num_layers, self.key_dim = int(num_layers), int(key_dim)
        if self.key_dim < 1:
            raise ValueError(f"key_dim must be positive, got {key_dim}")

    @property
    def row_lanes(self) -> int:
        """Width of one stored key: up to whole lane tiles."""
        return -(-self.key_dim // 128) * 128

    def pool_shape(self, num_pages: int, page_size: int):
        return (self.num_layers, int(num_pages), int(page_size),
                self.row_lanes)

    def bytes(self, num_pages: int, page_size: int, itemsize: int) -> int:
        """Device bytes of the pool, every page and layer."""
        return int(np.prod(self.pool_shape(num_pages, page_size))) \
            * int(itemsize)


class PageAllocator:
    """Host-side free list over page ids 1..num_pages-1 (0 is trash).

    A double free corrupts the pool silently (two slots end up writing
    the same page), so ``free`` detects it via a mirror set and raises
    LOUDLY instead."""

    def __init__(self, num_pages: int):
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._free_set = set(self._free)
        self._lock = threading.Lock()

    @property
    def num_free(self) -> int:
        with self._lock:
            return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take n pages, or None (atomically nothing) when the pool
        cannot cover the request."""
        if n <= 0:
            # guard the n==0 slice below (`self._free[-0:]` is the
            # WHOLE list, not an empty one) — a fully-shared claim
            # legitimately needs zero fresh pages
            return []
        with self._lock:
            if n > len(self._free):
                return None
            taken = self._free[-n:]
            del self._free[-n:]
            self._free_set.difference_update(taken)
            return list(reversed(taken))

    def free(self, pages: Sequence[int]) -> None:
        with self._lock:
            for p in pages:
                p = int(p)
                if p == 0:
                    continue
                if p in self._free_set:
                    raise RuntimeError(
                        f"double free of KV-cache page {p}: the page is "
                        f"already on the free list (refcount/lifecycle "
                        f"bug — a slot release or eviction ran twice)")
                self._free.append(p)
                self._free_set.add(p)


class _PrefixEntry:
    __slots__ = ("page_id", "parent", "tokens", "full", "children",
                 "tick")

    def __init__(self, page_id, parent, tokens, full, tick):
        self.page_id = page_id
        self.parent = parent
        self.tokens = tokens
        self.full = full
        self.children = 0
        self.tick = tick


class PrefixIndex:
    """Exact-content trie over registered (immutable) pages.

    Node key = ``(parent_page_id, tuple(page_tokens))`` — page ids are
    unique while resident, so the chain match is exact and a prompt can
    never hit a page holding different bytes (no hash collisions by
    construction).  Entries record their token content, so the FINAL
    partial page of a prompt can be matched as a token-prefix of a
    registered tail (the consumer then copy-on-writes at its first
    divergent token).  Single-threaded by contract: only the engine
    thread mutates it (admission / release / eviction)."""

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self._by_key: Dict[tuple, _PrefixEntry] = {}
        self._children: Dict[int, List[_PrefixEntry]] = {}
        self._by_page: Dict[int, _PrefixEntry] = {}
        self._tick = 0

    def __len__(self) -> int:
        return len(self._by_page)

    def is_registered(self, page_id: int) -> bool:
        return int(page_id) in self._by_page

    def lookup(self, prompt: Sequence[int]) -> Tuple[List[int],
                                                     Optional[int]]:
        """Longest registered prefix of ``prompt``: ``(full_pages,
        partial_page)`` — ordered page ids for every whole matched page
        and, when the REMAINING prompt tail is a token-prefix of a
        registered page's content, that page id (the CoW candidate).
        A partial hit therefore always means the ENTIRE prompt is
        cache-covered."""
        p = self.page_size
        prompt = [int(t) for t in prompt]
        n = len(prompt)
        self._tick += 1
        full: List[int] = []
        parent = 0
        while (len(full) + 1) * p <= n:
            toks = tuple(prompt[len(full) * p:(len(full) + 1) * p])
            e = self._by_key.get((parent, toks))
            if e is None:
                break
            e.tick = self._tick
            full.append(e.page_id)
            parent = e.page_id
        partial = None
        m = n - len(full) * p
        if m > 0:
            tail = tuple(prompt[len(full) * p:])
            for e in self._children.get(parent, ()):
                if len(e.tokens) >= m and e.tokens[:m] == tail:
                    e.tick = self._tick
                    partial = e.page_id
                    break
        return full, partial

    def register(self, pages: Sequence[int], tokens: Sequence[int],
                 on_new) -> int:
        """Register the chain of ``pages`` holding ``tokens`` (page i
        holds tokens[i*p:(i+1)*p]; the last page may be partial).  An
        existing identical entry is adopted as the chain parent and the
        caller's duplicate page is simply not registered (it frees
        normally).  ``on_new(page_id)`` is called for each page the
        index takes a reference on.  Returns newly registered count."""
        p = self.page_size
        tokens = [int(t) for t in tokens]
        parent = 0
        new = 0
        for i, pid in enumerate(pages):
            pid = int(pid)
            toks = tuple(tokens[i * p:(i + 1) * p])
            if not toks or pid == 0:
                break
            existing = self._by_key.get((parent, toks))
            if existing is not None:
                parent = existing.page_id
                if len(toks) < p:
                    break
                continue
            if pid in self._by_page:
                # the page is already registered under another key —
                # never alias one page into two trie positions
                break
            e = _PrefixEntry(pid, parent, toks, len(toks) == p,
                             self._tick)
            self._by_key[(parent, toks)] = e
            self._children.setdefault(parent, []).append(e)
            if parent in self._by_page:
                self._by_page[parent].children += 1
            self._by_page[pid] = e
            on_new(pid)
            new += 1
            if not e.full:
                break
            parent = pid
        return new

    def evict(self, n_pages: int, can_evict, on_evict) -> int:
        """Free up to ``n_pages`` pages by removing least-recently-hit
        CHILDLESS entries whose page ``can_evict(pid)`` approves (only
        the index references it).  Bottom-up by construction: an entry
        with children is never removed, so a freed-and-reused page id
        can never be mistaken for a live chain parent.  O(entries) per
        eviction — fine at host-bookkeeping scale."""
        freed = 0
        while freed < n_pages:
            victims = [e for e in self._by_page.values()
                       if e.children == 0 and can_evict(e.page_id)]
            if not victims:
                break
            e = min(victims, key=lambda v: v.tick)
            self._remove(e)
            on_evict(e.page_id)
            freed += 1
        return freed

    def _remove(self, e: _PrefixEntry) -> None:
        del self._by_key[(e.parent, e.tokens)]
        sibs = self._children[e.parent]
        sibs.remove(e)
        if not sibs:
            del self._children[e.parent]
        if e.parent in self._by_page:
            self._by_page[e.parent].children -= 1
        del self._by_page[e.page_id]


class ClaimInfo:
    """What an admission claim resolved to (prefix-cache accounting)."""

    __slots__ = ("hit_tokens", "full_hits", "partial", "hit_pages",
                 "prompt_pages", "fresh_pages")

    def __init__(self, hit_tokens, full_hits, partial, hit_pages,
                 prompt_pages, fresh_pages):
        self.hit_tokens = hit_tokens      # prompt positions cache-covered
        self.full_hits = full_hits        # whole shared pages
        self.partial = partial            # borrowed a partial tail page
        self.hit_pages = hit_pages        # full_hits + (1 if partial)
        self.prompt_pages = prompt_pages  # ceil(len(prompt)/page)
        self.fresh_pages = fresh_pages    # newly allocated pages


class PagedKVCache:
    """Host bookkeeping (page tables, lengths, refcounts, allocator,
    prefix index) + the device page arrays, which live in ``scope`` so
    Executor.run_persistent can donate them through each decode step."""

    def __init__(self, config: CacheConfig, scope, prefix_cache=True,
                 recurrent: Optional[RecurrentSpec] = None,
                 window: Optional[WindowSpec] = None,
                 index: Optional[IndexSpec] = None):
        import jax.numpy as jnp

        self.config = config
        self.scope = scope
        # slot-indexed slabs of the layers that keep state instead of
        # keys, slot-indexed rings of the layers that attend a window
        # (module header); with either there is no prefix index
        self.recurrent = recurrent if recurrent is not None \
            and recurrent.num_layers else None
        self.window = window if window is not None \
            and window.num_layers else None
        per_slot = self.recurrent is not None or self.window is not None
        # the index keys of layers that select what they attend: a
        # third pool behind the K/V pools' page ids (module header)
        self.index = index if index is not None and index.num_layers \
            else None
        if self.index is not None and config.quantized:
            raise ValueError(
                "an index pool's keys are scored as they lie: no kv_quant")
        # ... and none over latent rows or an index pool (module header)
        fresh_only = per_slot or config.latent or self.index is not None
        self.prefix_bypassed = bool(prefix_cache) and fresh_only
        prefix_cache = bool(prefix_cache) and not fresh_only
        # optional per-request tracing hook: ``on_event(slot, name,
        # **attrs)`` fired on cache lifecycle events (cow_swap, evict,
        # register) — the decode engine wires it to the owning
        # request's timeline (observe/request_trace.py); ``slot`` is
        # None for events with no slot owner (evictions during an
        # admission allocation)
        self.on_event = None
        self.allocator = PageAllocator(config.num_pages)
        self.prefix: Optional[PrefixIndex] = \
            PrefixIndex(config.page_size) if prefix_cache else None
        c = config
        # per-slot host mirrors: the scheduler reads/writes these; the
        # device sees them as small per-step i32 feeds
        self.page_table = np.zeros((c.num_slots, c.pages_per_slot),
                                   np.int32)
        self.lengths = np.zeros((c.num_slots,), np.int32)
        self._slot_pages: List[List[int]] = [[] for _ in range(c.num_slots)]
        # every page id a slot holds ONE reference on (table pages +
        # the CoW spare); release decrefs exactly this list
        self._slot_refs: List[List[int]] = [[] for _ in range(c.num_slots)]
        # reserved CoW target for a borrowed partial page (at most one)
        self._cow_spare: List[List[int]] = [[] for _ in range(c.num_slots)]
        self._refs = [0] * c.num_pages
        scope.set_var(K_PAGES_VAR, jnp.zeros(c.pool_shape(), c.store_dtype))
        if c.v_row_lanes:
            scope.set_var(V_PAGES_VAR, jnp.zeros(
                c.pool_shape(row_lanes=c.v_row_lanes), c.store_dtype))
        if self.index is not None:
            scope.set_var(INDEX_PAGES_VAR, jnp.zeros(
                self.index.pool_shape(c.num_pages, c.page_size),
                c.store_dtype))
        if self.window is not None:
            for var, shape in zip((WINDOW_K_VAR, WINDOW_V_VAR),
                                  self.window.pool_shapes(c.num_slots,
                                                          c.page_size)):
                scope.set_var(var, jnp.zeros(shape, c.store_dtype))
        # quantized mode: parallel per-page scale pools (one scale per
        # head per position-in-page), plus the freed-page reset queue
        # the scale audit relies on.  ``scale_vars`` also collects any
        # EXTRA scale pools sharing this cache's page ids (the decode
        # engine appends its draft-model scale pools) so resets and
        # audits cover every pool.
        self.scale_vars: List[str] = []
        self._pending_scale_resets: List[int] = []
        # pages installed by a disagg migration, while owned by their
        # admitting slot: page id -> slot.  An installed page is a
        # FRESH page (refcount exactly 1, never index-registered) until
        # its slot releases — debug_check audits exactly that.
        self._migrated_in: Dict[int, int] = {}
        if c.quantized:
            sshape = c.pool_shape(row_lanes=c.num_heads)
            scope.set_var(K_SCALES_VAR,
                          jnp.full(sshape, SCALE_EPS, c.scale_dtype))
            scope.set_var(V_SCALES_VAR,
                          jnp.full(sshape, SCALE_EPS, c.scale_dtype))
            self.scale_vars = [K_SCALES_VAR, V_SCALES_VAR]
        if self.recurrent is not None:
            spec = self.recurrent
            for var, (rshape, rdtype) in zip(
                    spec.var_names(),
                    list(spec.arrays.values()) * spec.num_layers):
                scope.set_var(var, jnp.zeros((c.num_slots,) + rshape,
                                             rdtype))

    def state_var_names(self) -> Tuple[str, ...]:
        """Scope names a persistent step must thread (in order): the
        two page pools, plus the scale pools when quantized, then the
        index pool, then the window layers' two pools, then the
        recurrent layers' slabs.  A latent cache and a joint one have
        the K pool alone."""
        names = (K_PAGES_VAR, V_PAGES_VAR) if self.config.v_row_lanes \
            else (K_PAGES_VAR,)
        if self.config.quantized:
            names += (K_SCALES_VAR, V_SCALES_VAR)
        return names + self.index_var_names() + self.window_var_names() \
            + self.recurrent_var_names()

    def index_var_names(self) -> Tuple[str, ...]:
        return (INDEX_PAGES_VAR,) if self.index is not None else ()

    def index_bytes(self) -> int:
        """Device bytes of the index keys' pool, all layers and pages
        (0 for a cache that keeps none)."""
        c = self.config
        return self.index.bytes(c.num_pages, c.page_size,
                                c.store_dtype.itemsize) \
            if self.index is not None else 0

    def window_var_names(self) -> Tuple[str, ...]:
        return (WINDOW_K_VAR, WINDOW_V_VAR) if self.window is not None \
            else ()

    def recurrent_var_names(self) -> Tuple[str, ...]:
        return self.recurrent.var_names() if self.recurrent is not None \
            else ()

    def window_bytes(self) -> int:
        """Device bytes of the window layers' pools, all slots."""
        c = self.config
        return self.window.bytes(c.num_slots, c.page_size,
                                 c.store_dtype.itemsize) \
            if self.window is not None else 0

    def latent_bytes(self) -> int:
        """Device bytes of the latent rows' pool, all cache layers and pages
        (0 for a cache of K and V)."""
        return self.config.cache_bytes() if self.config.latent else 0

    def window_pages_held(self) -> int:
        """Ring pages that hold a position of a live request, over the
        window layers: never more than ``slots * layers * ring``."""
        if self.window is None:
            return 0
        c = self.config
        ring = self.window.ring_pages(c.page_size)
        pages = -(-self.lengths // c.page_size)
        return int(np.minimum(pages, ring).sum()) * self.window.num_layers

    def state_bytes(self) -> int:
        """Device bytes of the recurrent layers' slabs, all slots."""
        return self.config.num_slots * self.recurrent.slot_bytes() \
            if self.recurrent is not None else 0

    def _fire(self, slot, name, **attrs) -> None:
        hook = self.on_event
        if hook is None:
            return
        try:
            hook(slot, name, **attrs)
        except Exception:  # noqa: BLE001 — instrumentation must never
            stat_add("request_trace_errors")  # corrupt cache bookkeeping

    # -- refcounts --------------------------------------------------------
    def _incref(self, pid: int) -> None:
        self._refs[pid] += 1

    def _decref(self, pid: int) -> None:
        r = self._refs[pid] = self._refs[pid] - 1
        if r < 0:
            raise RuntimeError(
                f"KV-cache page {pid} refcount went negative — a "
                f"release/eviction path dropped a reference it never "
                f"held")
        if r == 0:
            self.allocator.free([pid])
            self._migrated_in.pop(pid, None)
            if self.config.quantized:
                # hygiene + auditability: a freed page's scale plane is
                # reset to SCALE_EPS (flushed in one batched device op
                # at the end of the release/claim that freed it).  Not
                # load-bearing for numerics — the write path quantizes
                # each position with its own fresh scale and reads are
                # length-masked — but it makes "this page is free" an
                # observable device-side fact debug_check() can assert.
                self._pending_scale_resets.append(pid)

    def flush_scale_resets(self) -> None:
        """Apply pending freed-page scale resets to every scale pool
        (the cache's own + any engine-registered extras).  Runs in the
        owner thread between step dispatches — eager jax ops, never
        racing a donated in-flight step."""
        if not self._pending_scale_resets:
            return
        import jax.numpy as jnp

        pids = np.asarray(sorted(set(self._pending_scale_resets)),
                          np.int32)
        self._pending_scale_resets = []
        for name in self.scale_vars:
            arr = self.scope.get_var(name)
            self.scope.set_var(
                name, arr.at[:, pids].set(jnp.asarray(
                    SCALE_EPS, arr.dtype)))

    def refcount(self, pid: int) -> int:
        return self._refs[int(pid)]

    @property
    def shared_pages(self) -> int:
        """Pages currently pinned by the prefix index."""
        return len(self.prefix) if self.prefix is not None else 0

    def _alloc_evicting(self, n: int) -> Optional[List[int]]:
        """Allocate n pages, evicting cache-only prefix entries under
        pressure (least-recently-hit, childless first)."""
        pages = self.allocator.alloc(n)
        if pages is not None or self.prefix is None:
            return pages
        short = n - self.allocator.num_free
        evicted = self.prefix.evict(
            short, can_evict=lambda pid: self._refs[pid] == 1,
            on_evict=self._decref)
        if evicted:
            stat_add("decode_prefix_evictions", evicted)
            self._fire(None, "evict", pages=evicted)
        return self.allocator.alloc(n)

    # -- slot lifecycle ---------------------------------------------------
    def claim(self, slot: int, reserve_tokens: int,
              prompt: Optional[Sequence[int]] = None
              ) -> Optional[ClaimInfo]:
        """Reserve pages covering ``reserve_tokens`` positions for the
        slot, sharing every registered prefix page of ``prompt``; None
        when the pool can't cover the FRESH remainder (caller retries
        later).  Shared-aware worst case: ``total - shared_full`` fresh
        pages are taken either way — with a partial borrow one of them
        is held back as the CoW spare, so the later copy-on-write can
        never hit an empty pool."""
        total = self.config.pages_for(reserve_tokens)
        full_hits: List[int] = []
        partial: Optional[int] = None
        if self.prefix is not None and prompt is not None:
            full_hits, partial = self.prefix.lookup(prompt)
        hits = full_hits + ([partial] if partial is not None else [])
        # pin the matched pages BEFORE the eviction-backed allocation:
        # a just-matched childless tail page is index-only (refcount 1)
        # and would otherwise be a legal eviction victim — freed and
        # handed straight back as this claim's "fresh" page, aliasing
        # one physical page under two table roles
        for pid in hits:
            self._incref(pid)
        n_fresh = total - len(full_hits)
        fresh = self._alloc_evicting(n_fresh)
        if fresh is None and partial is not None:
            # drop the partial borrow under pressure: unpinned, its
            # page becomes an eviction candidate again, and the fresh
            # count is unchanged (the borrow traded its CoW spare for
            # a plain page) — so any reservation the submit-time check
            # admitted can still be satisfied instead of deadlocking
            # the queue head behind its own matched page
            self._decref(partial)
            partial = None
            hits = list(full_hits)
            fresh = self._alloc_evicting(n_fresh)
        if fresh is None:
            for pid in hits:
                self._decref(pid)  # still index-pinned: never frees
            return None
        for pid in fresh:
            self._incref(pid)
        table_pages = list(full_hits)
        rest = list(fresh)
        spare: List[int] = []
        if partial is not None:
            spare = [rest.pop(0)]
            table_pages.append(partial)
        table_pages += rest
        self._slot_pages[slot] = table_pages
        self._slot_refs[slot] = hits + fresh
        self._cow_spare[slot] = spare
        row = np.zeros((self.config.pages_per_slot,), np.int32)
        row[:len(table_pages)] = table_pages
        self.page_table[slot] = row
        self.lengths[slot] = 0
        self.flush_scale_resets()  # evictions may have freed pages
        prompt_len = len(prompt) if prompt is not None else 0
        hit_tokens = len(full_hits) * self.config.page_size
        if partial is not None:
            hit_tokens = prompt_len  # partial hit == full prompt cover
        return ClaimInfo(
            hit_tokens=hit_tokens, full_hits=len(full_hits),
            partial=partial is not None,
            hit_pages=len(full_hits) + (1 if partial is not None else 0),
            prompt_pages=self.config.pages_for(max(prompt_len, 1))
            if prompt is not None else 0,
            fresh_pages=len(fresh))

    def release(self, slot: int,
                register_tokens: Optional[Sequence[int]] = None) -> None:
        """Drop the slot's references.  When ``register_tokens`` is
        given (the token content whose K/V the slot's leading pages
        hold), those pages are first registered in the prefix index —
        the index takes its own reference, so registered pages survive
        the release for future prompts to share."""
        # a migrated-in page's owned-fresh invariant ends with its
        # slot: from here it is an ordinary page (registrable in the
        # index, sharable, freeable)
        for pid in self._slot_pages[slot]:
            self._migrated_in.pop(pid, None)
        if register_tokens and self.prefix is not None:
            n_pages = self.config.pages_for(len(register_tokens))
            new = self.prefix.register(
                self._slot_pages[slot][:n_pages], register_tokens,
                on_new=self._incref)
            if new:
                self._fire(slot, "register", pages=new,
                           tokens=len(register_tokens))
        for pid in self._slot_refs[slot]:
            self._decref(pid)
        self._slot_pages[slot] = []
        self._slot_refs[slot] = []
        self._cow_spare[slot] = []
        self.page_table[slot] = 0
        self.lengths[slot] = 0
        self.flush_scale_resets()

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slot_pages[slot])

    # -- disaggregated-serving page migration -----------------------------
    def export_pages(self, pages: Sequence[int]) -> Dict[str, object]:
        """Gather the given page ids out of EVERY pool this cache
        threads through the persistent step (data pages + scale planes
        when quantized) into fresh device arrays, keyed by pool var
        name.  Must run on the engine thread between step dispatches —
        the gather's operand ordering against the donated pools is then
        guaranteed by jax dispatch order, and its result never aliases
        a pool buffer, so the payload survives the source's next
        step."""
        if self.recurrent is not None:
            raise ValueError(
                "a cache with recurrent state exports no pages: the "
                "state of the layers without keys is not in them")
        if self.window is not None:
            raise ValueError(
                "a cache with window layers exports no pages: a slot's "
                "ring of them holds its last positions only")
        if self.config.latent:
            raise ValueError(
                "a cache of latent pages exports none: the hand-over is "
                "not built for a pool of one row for keys and values")
        if self.index is not None:
            raise ValueError(
                "a cache with an index pool exports no pages: the "
                "hand-over is not built to carry a third pool's rows")
        idx = np.asarray([int(p) for p in pages], np.int32)
        return {name: self.scope.get_var(name)[:, idx]
                for name in self.state_var_names()}

    def install_pages(self, slot: int, export: "KVPageExport") -> None:
        """Scatter a migrated payload into the slot's leading
        ``export.n_pages`` table pages (claimed fresh — a migrated
        admission never prefix-shares, so every destination page is
        solely owned).  Covers every pool the payload carries; records
        ``migrate_pages_total`` / ``migrate_bytes_total`` /
        ``migrate_seconds``.  Engine-thread-only, like every pool
        mutation."""
        import jax
        import jax.numpy as jnp

        t0 = time.monotonic()
        names = self.state_var_names()
        if set(export.arrays) != set(names):
            raise ValueError(
                f"migration payload pools {sorted(export.arrays)} do "
                f"not match destination pools {sorted(names)} — "
                f"source/destination kv_quant configs disagree")
        if export.page_size != self.config.page_size:
            raise ValueError(
                f"migration payload page_size {export.page_size} != "
                f"destination page_size {self.config.page_size}")
        dst = self._slot_pages[slot][:export.n_pages]
        if len(dst) < export.n_pages:
            raise ValueError(
                f"slot {slot} holds {len(dst)} pages but the payload "
                f"carries {export.n_pages}")
        idx = np.asarray(dst, np.int32)
        for name in names:
            pool = self.scope.get_var(name)
            arr = export.arrays[name]
            want = (pool.shape[0], export.n_pages) + tuple(pool.shape[2:])
            if tuple(arr.shape) != want:
                raise ValueError(
                    f"migration payload {name} shape "
                    f"{tuple(arr.shape)} != expected {want}")
            # the migration itself: a placed destination pool (a pinned
            # replica's, or one a mesh-sharded step has written) pulls
            # the payload to its own chips, device-to-device; a pool
            # that is not committed anywhere yet follows the payload
            if pool.committed:
                arr = jax.device_put(arr, pool.sharding)
            self.scope.set_var(
                name, pool.at[:, idx].set(jnp.asarray(arr, pool.dtype)))
        for pid in dst:
            self._migrated_in[pid] = slot
        stat_add("migrate_pages_total", export.n_pages)
        stat_add("migrate_bytes_total", export.nbytes)
        stat_time("migrate_seconds", time.monotonic() - t0)
        self._fire(slot, "migrate_install", pages=list(dst),
                   bytes=export.nbytes)

    # -- copy-on-write ----------------------------------------------------
    def writable(self, slot: int, position: int) -> bool:
        pid = int(self.page_table[slot][int(position)
                                        // self.config.page_size])
        if pid == 0:
            return True  # trash absorbs anything
        return self._refs[pid] == 1 and not (
            self.prefix is not None and self.prefix.is_registered(pid))

    def plan_cow(self, slot: int, positions: Sequence[int]
                 ) -> List[Tuple[int, int]]:
        """Make every page covering ``positions`` writable by the slot.
        Shared/registered pages are swapped for the slot's reserved
        spare (falling back to a fresh allocation, which the
        reservation accounting makes unreachable); the page table is
        updated NOW and the returned ``(src, dst)`` copies MUST be
        performed on-device by the caller before its next write
        dispatch."""
        plans: List[Tuple[int, int]] = []
        p = self.config.page_size
        for idx in sorted({int(pos) // p for pos in positions}):
            pid = int(self.page_table[slot][idx])
            if pid == 0 or self.writable(slot, idx * p):
                continue
            if self._cow_spare[slot]:
                dst = self._cow_spare[slot].pop()
            else:
                got = self._alloc_evicting(1)
                if got is None:
                    raise CacheExhaustedError(
                        f"copy-on-write for slot {slot} page index "
                        f"{idx} found an empty pool — the shared-aware "
                        f"reservation accounting is broken (a spare "
                        f"page should have been held at admission)")
                dst = got[0]
                self._incref(dst)
                self._slot_refs[slot].append(dst)
            self.page_table[slot][idx] = dst
            self._slot_pages[slot][idx] = dst
            self._slot_refs[slot].remove(pid)
            # shared pages are held by the index and/or other slots, so
            # this decref can never free the page mid-copy
            self._decref(pid)
            self._fire(slot, "cow_swap", src=pid, dst=dst,
                       page_index=idx)
            plans.append((pid, dst))
        return plans

    def write_coords(self, slot: int):
        """(page_id, offset) for the NEXT position of the slot."""
        t = int(self.lengths[slot])
        return (int(self.page_table[slot][t // self.config.page_size]),
                t % self.config.page_size)

    def arrays(self):
        return (self.scope.get_var(K_PAGES_VAR),
                self.scope.get_var(V_PAGES_VAR)
                if self.config.v_row_lanes else None)

    # -- integrity audit (chaos tests / debugging) ------------------------
    def debug_check(self) -> None:
        """Assert the refcount/free-list/index books balance: every
        page is exactly one of {free, referenced}, and each page's
        refcount equals index-pin + per-slot references.  When the
        cache is quantized the audit extends to scale-pool/page-pool
        agreement: every scale in every pool is finite, and every FREE
        page's scale plane is reset to ``SCALE_EPS`` (in every pool —
        the cache's own and any engine-registered draft pools).  Raises
        AssertionError with the discrepancy."""
        self.flush_scale_resets()
        want = [0] * self.config.num_pages
        for slot_refs in self._slot_refs:
            for pid in slot_refs:
                want[pid] += 1
        if self.prefix is not None:
            for pid in list(self.prefix._by_page):
                want[pid] += 1
        with self.allocator._lock:
            free = set(self.allocator._free)
            assert len(free) == len(self.allocator._free), \
                "free list holds duplicate pages"
        for pid in range(1, self.config.num_pages):
            assert self._refs[pid] == want[pid], (
                f"page {pid}: refcount {self._refs[pid]} != "
                f"{want[pid]} held references")
            in_free = pid in free
            assert in_free == (self._refs[pid] == 0), (
                f"page {pid}: refcount {self._refs[pid]} but "
                f"{'on' if in_free else 'not on'} the free list")
        # migrated-in pages (disagg): while owned by their admitting
        # slot an installed page is FRESH — exactly one reference (the
        # slot's), never pinned by the prefix index, and (quantized)
        # carrying the live scale plane the source wrote
        for pid, slot in self._migrated_in.items():
            assert self._refs[pid] == 1, (
                f"migrated-in page {pid} (slot {slot}): refcount "
                f"{self._refs[pid]} != 1 — a migrated page leaked into "
                f"sharing before its slot released")
            assert self.prefix is None or \
                not self.prefix.is_registered(pid), (
                    f"migrated-in page {pid} (slot {slot}) is "
                    f"registered in the prefix index while still "
                    f"slot-owned")
            assert pid in self._slot_pages[slot], (
                f"migrated-in page {pid} not in slot {slot}'s table")
        if self.config.quantized and self._migrated_in:
            mig_idx = np.asarray(sorted(self._migrated_in), np.int32)
            for name in self.scale_vars:
                plane = np.asarray(self.scope.get_var(name))[:, mig_idx]
                assert np.isfinite(plane).all() and (plane > 0).all(), (
                    f"scale pool {name}: migrated-in pages "
                    f"{mig_idx.tolist()} hold non-finite/non-positive "
                    f"scales — the migration dropped a scale plane")
        if self.window is not None:
            # the rings: a slot's pages are its own by arithmetic, so
            # what can go wrong is the table or the pools' size, and a
            # ring that claims more pages than it has
            c = self.config
            ring = self.window.ring_pages(c.page_size)
            table = self.window.ring_table(c.num_slots, c.page_size)
            assert table.min() == 1 and len(set(table.ravel().tolist())) \
                == c.num_slots * ring, "window rings share a page"
            for var, shape in zip(self.window_var_names(),
                                  self.window.pool_shapes(c.num_slots,
                                                          c.page_size)):
                got = tuple(self.scope.get_var(var).shape)
                assert got == shape and got[1] == table.max() + 1, (
                    f"window pool {var} is {got}, not {shape}: its "
                    f"size must not follow the sequence length")
            assert self.window_pages_held() <= \
                c.num_slots * self.window.num_layers * ring
        if not self.config.quantized:
            return
        free_idx = np.asarray(sorted(free), np.int32)
        for name in self.scale_vars:
            arr = np.asarray(self.scope.get_var(name))
            assert np.isfinite(arr).all(), (
                f"scale pool {name} holds non-finite scales — a write "
                f"path stored an unclamped/overflowed scale")
            assert (arr > 0).all(), (
                f"scale pool {name} holds non-positive scales")
            if len(free_idx):
                stale = arr[:, free_idx]
                assert np.all(stale == np.float32(SCALE_EPS)), (
                    f"scale pool {name}: freed pages "
                    f"{free_idx[np.argwhere(np.any(stale != np.float32(SCALE_EPS), axis=(0, 2, 3)))].ravel().tolist()} "
                    f"kept live scales — a free path skipped the reset")


# -- pure jit-side helpers (operate on the page arrays functionally) ------

def _fold_heads(val):
    """[..., H, D] -> [..., H*D]: a position's heads side by side in
    one pool row (row-major, so free)."""
    return val.reshape(val.shape[:-2] + (val.shape[-2] * val.shape[-1],))


def _pool_rows(val, lanes: int):
    """``val``'s heads folded into rows of the pool's ``lanes``: a row
    narrower than the pool's (a latent's, stored at whole lane tiles)
    is filled with zeros."""
    import jax.numpy as jnp

    rows = _fold_heads(val)
    short = lanes - rows.shape[-1]
    if not short:
        return rows
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, short),))


def scatter_token_layer(pages, layer, val, page_id, offset):
    """Write one new position per row: val [R, H, D] lands as the row
    [R, H*D] at (layer, page_id[r], offset[r]) of pages [L, P, page,
    H*D] — dead rows pass page 0 (trash).  ``layer`` is the CACHE layer:
    a Python int, or a traced int32 scalar where the model's layers run
    in a rolled loop (the write is then a scatter at a traced index,
    still in place in a pool the loop carries).  Indexing the three leading
    axes of a lane-dense pool is what lets the chip scatter in place."""
    return pages.at[layer, page_id, offset].set(
        _pool_rows(val, pages.shape[-1]).astype(pages.dtype))


def scatter_prompt_layer(pages, layer, val, page_ids):
    """Write a whole prompt's positions for one slot: val
    [n_pages*page, H, D] (padded to a page multiple) is stored page-
    wholesale, as [n_pages, page, H*D], into ``page_ids`` [n_pages]."""
    n = page_ids.shape[0]
    page = pages.shape[2]
    v = _pool_rows(val, pages.shape[-1]).reshape(n, page, -1)
    return pages.at[layer, page_ids].set(v.astype(pages.dtype))


def quantize_kv(val):
    """Symmetric int8 quantization of K/V values at per-position
    per-head granularity: ``val [..., H, D] -> (q int8 [..., H, D],
    scale f32 [..., H])`` with the scale clamped PER SLICE (an all-zero
    head stores exact zeros instead of dividing by ~0 — the
    quant_ops._abs_max per-slice-clamp contract).  Pure and
    position-local, so every write path (single-token decode, chunked
    prefill rows, whole-prompt prefill, speculative re-writes) produces
    IDENTICAL stored bytes for identical values — the order-independence
    the bitwise spec/chunk composition tests pin."""
    import jax.numpy as jnp

    v = val.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(v), axis=-1) / KV_QMAX,
                        SCALE_EPS)
    q = jnp.clip(jnp.round(v / scale[..., None]), -KV_QMAX, KV_QMAX) \
        .astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype):
    """Inverse of :func:`quantize_kv` (broadcast the per-position
    per-head scale back over head_dim)."""
    import jax.numpy as jnp

    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def write_token_layer(pages, scales, layer, val, page_id, offset):
    """Quantization-aware :func:`scatter_token_layer`: returns
    ``(pages, scales)``.  ``scales=None`` is the unquantized path
    (pages store ``val`` directly, scales pass through); otherwise the
    int8 row [R, H*D] and its scale row [R, H] land at the same
    (layer, page, offset) of their pools."""
    if scales is None:
        return scatter_token_layer(pages, layer, val, page_id,
                                   offset), None
    q, s = quantize_kv(val)
    return (scatter_token_layer(pages, layer, q, page_id, offset),
            scales.at[layer, page_id, offset].set(
                s.astype(scales.dtype)))


def write_prompt_layer(pages, scales, layer, val, page_ids):
    """Quantization-aware :func:`scatter_prompt_layer`: returns
    ``(pages, scales)``; page-wholesale like the unquantized path, but
    each position quantizes independently — bitwise-identical bytes to
    the per-row chunked path writing the same values."""
    if scales is None:
        return scatter_prompt_layer(pages, layer, val, page_ids), None
    q, s = quantize_kv(val)
    n = page_ids.shape[0]
    return (scatter_prompt_layer(pages, layer, q, page_ids),
            scales.at[layer, page_ids].set(
                s.reshape(n, -1, s.shape[-1]).astype(scales.dtype)))


def write_window_prompt_layer(pages, layer: int, val, length, ring_row):
    """The whole-prompt prefill's write into a window layer's ring: of
    ``val`` [n_pages*page, H, D] (the prompt padded to its bucket, of
    which ``length`` positions are real) only the LAST ``ring`` pages
    that hold a real position are stored, logical page j at ``ring_row[j
    % ring]`` (``ring_row`` [ring]: the slot's page ids); where the prompt
    has fewer, the rest of the writes aim at page 0 (trash)."""
    import jax.numpy as jnp

    ring, page = ring_row.shape[0], pages.shape[2]
    v = _fold_heads(val).reshape(-1, page, pages.shape[3])
    last = (length - 1) // page                    # the newest real page
    logical = last - (ring - 1) + jnp.arange(ring, dtype=jnp.int32)
    ids = jnp.where(logical >= 0, ring_row[logical % ring], 0)
    src = jnp.take(v, jnp.clip(logical, 0, v.shape[0] - 1), axis=0)
    return pages.at[layer, ids].set(src.astype(pages.dtype))
