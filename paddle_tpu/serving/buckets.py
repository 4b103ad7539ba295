"""Shape bucketing for the serving layer.

The Executor's compile cache holds one XLA executable per distinct feed
shape, so a variable-length request stream compiles an executable per
length — a compile storm that leaves the chip idle exactly when traffic
arrives.  A ``BucketSpec`` pins the shape universe up front: every
request is padded UP to the smallest configured (batch-size,
sequence-length) bucket that holds it, so the cache holds exactly
``len(batch_sizes) * len(seq_lens)`` executables and the serving warmup
can pre-compile all of them before the first request.

Padding contract: the pad value (default 0) must be semantically inert
for the model — true for row-wise inference nets whose padded positions
are masked or contribute zeros (embedding-sum, relu-matmul chains,
attention with an explicit mask input).  Padded BATCH rows are always
sliced off before results are returned, so only padded SEQUENCE
positions can observe the pad value; symmetrically, a FETCH whose shape
retains a dynamic inner dim is returned padded to its seq bucket (the
server cannot know which output axes track the input length) — reduce
or mask such dims in-model, or slice client-side.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


class ServingError(RuntimeError):
    """Base class for serving-layer failures."""


class RequestTooLargeError(ServingError):
    """A request exceeds the largest configured bucket."""


class QueueFullError(ServingError):
    """Backpressure: the bounded request queue is at capacity."""


class DeadlineExceededError(ServingError):
    """The request's deadline passed before a result was produced."""


class ServerClosedError(ServingError):
    """The server is draining or stopped and accepts no new requests."""


class RequestAbandonedError(ServingError):
    """The client explicitly abandoned the request (RequestBase.abandon);
    the engine frees its slot/queue entry at the next boundary."""


class BucketSpec:
    """The static bucket grid: batch sizes x sequence lengths.

    ``batch_sizes`` bounds how many rows one compiled executable
    processes; ``seq_lens`` bounds every dynamic (declared ``-1``)
    non-batch feed dim.  ``seq_lens=None`` means the model has no
    dynamic inner dims (or the caller accepts one executable per
    distinct inner shape).
    """

    def __init__(self, batch_sizes: Sequence[int] = (1, 2, 4, 8),
                 seq_lens: Sequence[int] = None):
        bs = sorted({int(b) for b in batch_sizes})
        if not bs or bs[0] < 1:
            raise ValueError(f"batch_sizes must be positive ints, got "
                             f"{batch_sizes!r}")
        self.batch_sizes: Tuple[int, ...] = tuple(bs)
        if seq_lens is None:
            self.seq_lens = None
        else:
            sl = sorted({int(s) for s in seq_lens})
            if not sl or sl[0] < 1:
                raise ValueError(f"seq_lens must be positive ints, got "
                                 f"{seq_lens!r}")
            self.seq_lens = tuple(sl)

    @property
    def max_batch(self) -> int:
        return self.batch_sizes[-1]

    def n_buckets(self) -> int:
        return len(self.batch_sizes) * len(self.seq_lens or (None,))

    def batch_bucket(self, n: int) -> int:
        for b in self.batch_sizes:
            if b >= n:
                return b
        raise RequestTooLargeError(
            f"batch of {n} rows exceeds the largest configured batch "
            f"bucket {self.max_batch}")

    def seq_bucket(self, length: int) -> int:
        if self.seq_lens is None:
            return int(length)  # exact-shape mode: no inner padding
        for s in self.seq_lens:
            if s >= length:
                return s
        raise RequestTooLargeError(
            f"sequence length {length} exceeds the largest configured "
            f"seq bucket {self.seq_lens[-1]}")


def feed_plans(program, feed_names) -> Dict[str, tuple]:
    """The model's feed contract: name -> (declared shape, np dtype).

    Serving requires every feed's leading dim to be the dynamic batch
    dim (that is what gets coalesced); a model exported with a static
    batch cannot be micro-batched and is rejected loudly here rather
    than producing shape errors under traffic.
    """
    from ..framework import dtypes

    block = program.global_block
    plans: Dict[str, tuple] = {}
    for name in feed_names:
        var = block._find_var_recursive(name)
        if var is None:
            raise KeyError(f"feed var {name!r} not found in program")
        shape = tuple(int(s) for s in (var.shape or ()))
        if not shape or shape[0] not in (-1, 0):
            raise ValueError(
                f"feed {name!r} declares shape {shape}: serving needs a "
                f"dynamic (-1) leading batch dim to coalesce requests")
        plans[name] = (shape, dtypes.to_np(var.dtype))
    return plans


def plan_request(feeds: Dict[str, np.ndarray], plans: Dict[str, tuple],
                 spec: BucketSpec):
    """Validate one request against the feed contract and compute its
    coalescing key.

    Returns ``(arrays, nrows, key)`` where ``key`` is the tuple of
    per-feed padded inner shapes — two requests coalesce iff their keys
    are equal (they pad to the same executable).  Raises
    ``RequestTooLargeError`` when any dim exceeds the bucket grid, and
    plain ``KeyError``/``ValueError`` for contract violations.
    """
    missing = [n for n in plans if n not in feeds]
    if missing:
        raise KeyError(f"missing inputs: {missing}")
    arrays: Dict[str, np.ndarray] = {}
    nrows = None
    key: List[tuple] = []
    for name in sorted(plans):
        shape, np_dtype = plans[name]
        arr = np.asarray(feeds[name])
        if arr.dtype != np_dtype:
            arr = arr.astype(np_dtype)
        if arr.ndim != len(shape):
            raise ValueError(
                f"feed {name!r}: rank {arr.ndim} != declared rank "
                f"{len(shape)} {shape}")
        if arr.shape[0] < 1:
            raise ValueError(f"feed {name!r} has an empty batch dim")
        if nrows is None:
            nrows = int(arr.shape[0])
        elif int(arr.shape[0]) != nrows:
            raise ValueError(
                f"feeds disagree on the batch dim: {name!r} has "
                f"{arr.shape[0]} rows, earlier feeds have {nrows}")
        if nrows > spec.max_batch:
            raise RequestTooLargeError(
                f"request batch {nrows} exceeds the largest configured "
                f"batch bucket {spec.max_batch}")
        inner = []
        for d_decl, d_act in zip(shape[1:], arr.shape[1:]):
            if d_decl in (-1, 0):
                inner.append(spec.seq_bucket(int(d_act)))
            elif int(d_decl) != int(d_act):
                raise ValueError(
                    f"feed {name!r}: shape {tuple(arr.shape)} does not "
                    f"match declared {shape}")
            else:
                inner.append(int(d_act))
        arrays[name] = arr
        key.append((name, tuple(inner)))
    return arrays, nrows, tuple(key)


def assemble(requests, key, spec: BucketSpec, pad_value=0):
    """Coalesce same-key requests into one padded bucket batch.

    Rows concatenate in request order; dynamic inner dims pad to the
    key's bucketed extents; the batch dim pads up to its batch bucket.
    Returns ``(feed dict, total live rows, bucket batch)`` — callers
    slice results back out with the per-request row counts.
    """
    total = sum(r.nrows for r in requests)
    bucket_rows = spec.batch_bucket(total)
    feeds: Dict[str, np.ndarray] = {}
    for name, inner in key:
        parts = []
        for r in requests:
            a = r.feeds[name]
            widths = [(0, 0)] + [(0, t - s)
                                 for t, s in zip(inner, a.shape[1:])]
            if any(w[1] for w in widths):
                a = np.pad(a, widths, constant_values=pad_value)
            parts.append(a)
        if bucket_rows > total:
            parts.append(np.full((bucket_rows - total,) + tuple(inner),
                                 pad_value, parts[0].dtype))
        feeds[name] = parts[0] if len(parts) == 1 \
            else np.concatenate(parts, axis=0)
    return feeds, total, bucket_rows


def bucket_feed_specs(plans: Dict[str, tuple], spec: BucketSpec):
    """Enumerate the warmup grid: one Executor feed spec per bucket.

    Models with no dynamic inner dims collapse the seq axis (the grid
    de-duplicates); models WITH dynamic inner dims but ``seq_lens=None``
    have an open-ended shape universe and return only what is closed —
    the caller should warn that warmup cannot cover exact-shape mode.
    """
    specs = []
    seen = set()
    open_ended = spec.seq_lens is None and any(
        any(d in (-1, 0) for d in shape[1:])
        for shape, _ in plans.values())
    if open_ended:
        return [], True
    for b in spec.batch_sizes:
        for s in (spec.seq_lens or (None,)):
            fs = {}
            for name, (shape, np_dtype) in plans.items():
                dims = [b] + [s if d in (-1, 0) else int(d)
                              for d in shape[1:]]
                fs[name] = (tuple(dims), np_dtype)
            fp = tuple(sorted((n, v[0], str(np.dtype(v[1])))
                              for n, v in fs.items()))
            if fp not in seen:
                seen.add(fp)
                specs.append(fs)
    return specs, False


def prefill_bucket_grid(max_seq_len: int, page_size: int):
    """Prompt-length buckets for the decode engine's prefill compiles
    (serving/decode.py): page-multiple powers of two capped at
    max_seq_len, so the prefill executable universe stays
    O(log(max_seq/page)) and every bucket scatters whole KV pages.

    The rounding buys a tiny executable universe at the price of dead
    query rows — a 65-token prompt dispatches a 128-row executable.
    Every admission must account that waste through
    ``record_pad_waste`` so the cost is measurable."""
    out = []
    b = int(page_size)
    while b < max_seq_len:
        out.append(b)
        b *= 2
    out.append(int(max_seq_len))
    return tuple(out)


def record_pad_waste(live_tokens: int, dispatched_tokens: int) -> None:
    """Account one prefill dispatch's padding: ``dispatched - live``
    query rows computed attention for nobody.  Keeps the running
    counters and re-derives the ``prefill_pad_waste`` gauge (cumulative
    padded fraction of all dispatched prefill rows, in parts-per-million
    — the stat registry is integer-only)."""
    from ..monitor import stat_add, stat_get, stat_set

    live = max(0, int(live_tokens))
    pad = max(0, int(dispatched_tokens) - live)
    stat_add("prefill_padded_tokens_total", pad)
    stat_add("prefill_live_tokens_total", live)
    padded = stat_get("prefill_padded_tokens_total")
    total = padded + stat_get("prefill_live_tokens_total")
    if total:
        stat_set("prefill_pad_waste", int(padded * 1_000_000 / total))
