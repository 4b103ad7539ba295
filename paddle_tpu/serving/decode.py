"""KV-cache autoregressive decode engine with continuous batching,
prefix-cache page sharing, chunked prefill, and speculative decoding.

Role parity: the generative-serving half of Paddle Serving / the
reference's inference deployment story — the piece the PR-1 one-shot
bucket batcher cannot cover, because autoregressive decode re-enters
the model once PER TOKEN.  Recomputing the prefix every token is
O(len^2) per request; waiting for a shape bucket adds whole-batch
latency to every new arrival.  This engine is the TPU-native fix:

- **Persistent per-slot KV cache** (`kv_cache.py`): each of the
  ``slots`` concurrent requests owns paged key/value blocks inside two
  device-resident pool arrays (a model's window layers: a ring of
  pages a slot in pools of their own; its recurrent layers: slabs).
  The pools ride
  ``Executor.run_persistent`` with donation, so the cache NEVER
  round-trips to host between steps — per-token work is O(1) in the
  prefix length.
- **Prefix sharing** (``FLAGS_decode_prefix_cache``, default on): at
  millions of users most prompts open with the same system/template
  prefix.  Finished requests register their pages in an exact-content
  trie; admission shares every matched page into the new slot's table
  with a refcount bump — skipping both the HBM reservation AND the
  prefill compute for hit pages (an exactly-matched prompt skips
  prefill entirely: the first token comes out of the first decode
  step).  A borrowed partial tail page is copy-on-written at the first
  divergent token, from a spare reserved at admission so a decode step
  still can never die on cache exhaustion.
- **Chunked prefill** (``FLAGS_decode_prefill_chunk_pages``): a long
  prompt fills its pages across SEVERAL step boundaries (one chunk per
  engine-loop iteration) instead of stalling the whole slot batch on
  one long prefill dispatch — the slots already decoding keep emitting
  tokens, protecting ``ttft_ms_p99`` for everyone else.
- **Speculative decoding** (``FLAGS_decode_spec_k`` + a draft model):
  a small draft proposes k tokens in ONE device dispatch (its own page
  pools share the target's page ids, so prefix sharing and CoW cover
  it for free) and the target verifies all k+1 positions in ONE
  batched step.  Greedy output is BITWISE-identical to non-speculative
  decode: every emitted token is the target's own argmax, proposals
  only decide how many arrive per dispatch.
- **Continuous batching** (Orca's iteration-level scheduling): one
  jitted step decodes every live slot jointly; new requests claim free
  slots at step boundaries, and a slot whose request finishes — EOS,
  token budget, or deadline — frees IMMEDIATELY instead of padding to
  the longest neighbor.
- **Deadline reap mid-decode**: a lapsed deadline is honored at every
  step boundary (not just at dequeue), so a stalled client cannot pin
  a slot for the full max_new_tokens.
- **Streaming replies**: each sampled token is pushed to the request's
  stream the step it is produced — consume via the ``tokens()``
  generator or an ``on_token`` callback; ``result()`` blocks for the
  full sequence.
- **Deterministic sampling** (`ops/sampling_ops.py`): greedy / top-k /
  top-p run INSIDE the compiled step with an explicit per-request PRNG
  key (seed + fold_in(token index)), so a request's tokens are
  independent of slot assignment, batch composition, and replica —
  the property multi-replica scale-out (serving/server.py
  ``DecodeServer``) relies on.

Attention reads the page pool through
``ops/pallas_decode_attention.py``: the Pallas kernels on TPU (page
table as scalar-prefetch operands — a slot's live pages copied a block
of them at a time), the
pure-jnp gather+mask reference on CPU so tier-1 stays green.  Every
path — prefill, chunked prefill, decode, speculative verify — shares
ONE masked-softmax formulation (the cache's width, or a whole prompt's
bucket: masked positions weigh zero), which keeps decode-with-cache
logits bitwise-equal to a full recompute (`tests/test_decode_engine.py`
+ `tests/test_decode_prefix_spec.py` pin it every step on every path).

Observability: ``decode_*`` counters/gauges (``decode_cache_hit_rate``,
``decode_shared_pages``, ``decode_cow_copies``, ``spec_accept_rate``,
``prefill_chunks``, ...) plus ``ttft_seconds`` / ``tpot_seconds`` /
``decode_step_seconds`` histograms — all on ``/metrics`` wherever a
fleet KV HTTP server runs.  One iteration of the engine thread is a row
of leaf spans that follow one another (``observe/tracer.py``: events of
a running ``jax.profiler`` trace, ring-buffer records under
``FLAGS_enable_tracer``): ``serving/reap`` | per decode round
``serving/step_cow`` | ``step_args`` | then ``serving/lock_wait`` |
``serving/admit`` (or ``serving/idle_wait``) | per whole-prompt prefill
``serving/prefill_args`` | ``prefill_dispatch`` | then
``serving/step_dispatch`` | then, of the joint step handed over an
iteration AGO, ``serving/step_sync`` | ``step_deliver`` | then per
prefill ``serving/prefill_sync`` | ``prefill_deliver`` (a chunked or
suffix prefill runs its four phases in a row where the whole-prompt one
is dispatched; a round that holds a speculative slot
reads its own joint step, behind the prefills).  A joint step is handed
over while the one before it is in flight (``DecodeEngine._loop``): a
step's five leaves share its ``step`` number, fixed at the hand-over,
across two iterations, and ``step_dispatch`` says how many joint steps
were in flight then (``in_flight``, 0 or 1; counters
``decode_steps_ahead``, ``decode_rows_discarded``).  No span encloses
the iteration, so a
device gap is named by the phase the host was in.  Every leaf carries
``iter`` (the loop's own ordinal: a period's spans group by it).  The
``*_args`` spans
carry the host arrays their builder handed to the device (``uploads``,
``upload_bytes``; counters ``decode_h2d_uploads`` / ``decode_h2d_bytes``);
the ``*_deliver`` spans, while a profiler session or the ring buffer
takes them, what they carried (``tokens``, ``finished``, ``emit_ms`` in
the callers' ``on_token`` and stream, ``finish_ms`` in
``_finish_slot``).  ``decode_step_seconds`` runs from a step's hand-over
to its tokens on the host (behind a step in flight: that step's
remainder and the host's path beside it included);
``decode_turnaround_seconds``, tokens on the host until the next joint
step's hand-over begins, is observed only at a hand-over with nothing
in flight: where the device waits for the host.  A step whose hand-over
and read-back together keep the host longer than ``SLOW_STEP_S`` (the
program's first run apart) leaves a ``serving/slow_step`` event in the
flight recorder and counts ``decode_steps_slow``.
The joint step and the whole-prompt prefill take everything after the
weights as ONE packed int32 array (``_words`` / ``_unpack``): one upload
a dispatch, whatever the number of fields.
"""
from __future__ import annotations

import collections
import inspect
import queue as _queue
import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from ..monitor import stat_add, stat_get, stat_max, stat_set
from ..observe import tracer as otrace
from ..observe.histogram import stat_time
from .batcher import _UNSET, RequestBase
from .buckets import (BucketSpec, DeadlineExceededError, QueueFullError,
                      RequestTooLargeError, ServerClosedError,
                      prefill_bucket_grid, record_pad_waste)
from . import kv_cache
from .kv_cache import (CacheConfig, IndexSpec, PagedKVCache, RecurrentSpec,
                       WindowSpec)
# the reference model lives beside the engine; its names stay importable here
from .transformer_lm import (TransformerLM, quantize_moe_weights,  # noqa: F401
                             shard_moe_weights)


class _Uploads:
    """Counts the host arrays one dispatch's argument builder hands to
    the device, at the sites that hand them over: one packed array for
    the joint step and the whole-prompt prefill, one a field for the
    rows and speculative builders."""

    __slots__ = ("n", "nbytes")

    def __init__(self):
        self.n = self.nbytes = 0

    def __call__(self, a):
        """``jnp.asarray(a)`` of a host array: one upload."""
        import jax.numpy as jnp

        return jnp.asarray(self.host(a))

    def host(self, a):
        """A host value left as it is for the dispatch to upload."""
        self.n += 1
        self.nbytes += a.nbytes
        return a

    def record(self):
        """Into the counters and onto the open ``*_args`` span."""
        stat_add("decode_h2d_uploads", self.n)
        stat_add("decode_h2d_bytes", self.nbytes)
        otrace.set_span_args(uploads=self.n, upload_bytes=self.nbytes)


class _Delivery:
    """A ``*_deliver`` leaf that says what it carried.  While a sink
    takes the span (a profiler session or the ring buffer, asked once
    as it opens) ``_deliver`` counts into the engine's ``_carried``:
    tokens, slots that ended, ns in ``req._emit``, ns in
    ``_finish_slot``; with no sink ``_carried`` is None and ``_deliver``
    reads no clock.  A class, not a generator: what opens and closes a
    leaf lies between two spans, under no phase's name."""

    __slots__ = ("_engine", "_span")

    def __init__(self, engine, name, attrs):
        self._engine = engine
        self._span = otrace.span(name, **attrs)

    def __enter__(self):
        self._span.__enter__()
        self._engine._carried = [0, 0, 0, 0] if otrace.recording() \
            else None
        return self

    def __exit__(self, *exc):
        carried, self._engine._carried = self._engine._carried, None
        if carried is not None:
            tokens, finished, emit_ns, finish_ns = carried
            otrace.set_span_args(tokens=tokens, finished=finished,
                                 emit_ms=emit_ns * 1e-6,
                                 finish_ms=finish_ns * 1e-6)
        return self._span.__exit__(*exc)


# what the host may wait in a joint step's hand-over and read-back
# together before the step leaves a ``serving/slow_step`` record: 7 times
# the longest prefill of any benchmarked cell, so no healthy step passes
# it.  (Between the two the host runs its own path beside the device.)
SLOW_STEP_S = 0.5


def _rid(req):
    """The request's id, as ``submit`` minted it (spans' ``req``)."""
    return req.trace.trace_id if req.trace is not None else ""


# -- packed arguments -------------------------------------------------------
# What a dispatch takes after its weights travels as ONE int32 host array.
# A numpy record dtype names the fields; every field is four bytes wide, so
# the records' own memory viewed as int32 IS the upload (float32 and uint32
# fields by their bit pattern, never through a cast), and the jitted body
# slices the words back into the same fields.

_SAMPLING = [("key", np.uint32, (2,)), ("temperature", np.float32),
             ("top_k", np.int32), ("top_p", np.float32)]
_LIVE, _TRASH, _CARRY = 1, 2, 4  # bits of a step row's ``flags``


def _step_row(pages_per_slot: int) -> np.dtype:
    """One slot of the joint decode step; ``flags`` holds ``_LIVE``,
    ``_TRASH`` (``write_trash_once``: the write aims at page 0, offset
    0) and ``_CARRY`` (the slot was live in the joint step in flight:
    its token is that step's, still on the device, and ``token`` is not
    read), ``pages`` the slot's page-table row."""
    return np.dtype([("token", np.int32), ("position", np.int32),
                     ("flags", np.int32), ("counter", np.int32)]
                    + _SAMPLING + [("pages", np.int32, (pages_per_slot,))])


def _prefill_row(t_pad: int, pages_per_slot: int,
                 slot: bool = False) -> np.dtype:
    """One whole-prompt prefill at bucket ``t_pad``; with ``slot`` (a
    model with recurrent layers) also the slot whose state rows it
    leaves the prompt's final state in."""
    return np.dtype([("tokens", np.int32, (t_pad,)), ("length", np.int32)]
                    + _SAMPLING + [("pages", np.int32, (pages_per_slot,))]
                    + ([("slot", np.int32)] if slot else []))


def _words(records: np.ndarray) -> np.ndarray:
    """The int32 words of a record array, ``[..., words a record]``: the
    same memory, which is what is uploaded."""
    return records.view(np.int32).reshape(records.shape + (-1,))


def _unpack(words, row: np.dtype) -> dict:
    """Inside a jitted body: ``words`` (``_words`` of ``row`` records)
    sliced back into the fields, each in its own dtype by bit pattern."""
    from jax import lax

    fields = {}
    for name in row.names:
        field, offset = row.fields[name][:2]
        w = words[..., offset // 4:(offset + field.itemsize) // 4]
        if field.base != np.int32:
            w = lax.bitcast_convert_type(w, field.base)
        fields[name] = w.reshape(words.shape[:-1] + field.shape)
    return fields


def _key_words(seed: int) -> np.ndarray:
    """The two uint32 words of ``jax.random.PRNGKey(seed)`` (threefry
    over jax's 32-bit integers: a zero word, then the seed's low 32
    bits), made on the host."""
    return np.array([0, seed & 0xFFFFFFFF], np.uint32)


WINDOW_SCOPE = "window_attention"   # names a window layer's attention ops
LATENT_SCOPE = "latent_attention"   # ... and a latent layer's, in the step

DRAFT_K_PAGES_VAR = "__decode_draft_k_pages__"
DRAFT_V_PAGES_VAR = "__decode_draft_v_pages__"
DRAFT_K_SCALES_VAR = "__decode_draft_k_scales__"
DRAFT_V_SCALES_VAR = "__decode_draft_v_scales__"

# the target-model state tuple comes from PagedKVCache.state_var_names()
# (page pools + scale pools when quantized); only the draft tuple is
# assembled here
_DRAFT_VARS = (DRAFT_K_PAGES_VAR, DRAFT_V_PAGES_VAR)
_DONE = object()  # stream sentinel


def _split_state(state):
    """Persistent-state tuple -> (k_pages, v_pages, k_scales,
    v_scales); the scale pools exist only under FLAGS_decode_kv_quant,
    and a latent or a joint cache's one pool comes alone (``v_pages``
    None: the values are lanes of K's rows)."""
    kp, *rest = state
    vp, *scales = rest or (None,)
    return (kp, vp, *(scales or (None, None)))


def _join_state(pools):
    return tuple(p for p in pools if p is not None)


_KINDS = ("attention", "window", "recurrent")  # what a layer may be
# what a whole-prompt prefill counts of its recurrent layers' scans, a
# layer: iterations of the loop, and the real tokens they took
_SCAN_TALLIES = ("decode_prefill_scan_steps", "decode_prefill_scan_tokens")


class _Mixed:
    """The layout of a model whose layers are not all attention (it
    declares ``layer_kinds``): which pool layer an attention layer's K/V
    live in, which layer of the window pools a window layer's, which
    slabs a recurrent layer's state, and how the persistent-state tuple
    (pools, then the index pool, then the window pools, then slabs
    layer-major) splits into the ``(pools, window pools, recurrent)``
    cache that ``forward`` threads; an index pool rides behind the four
    ``pools`` as a fifth."""

    def __init__(self, model, spec: Optional[RecurrentSpec],
                 window: Optional[WindowSpec], slots: int,
                 index: Optional[IndexSpec] = None):
        self.kinds = tuple(model.layer_kinds)
        self.layer = {kind: {l: i for i, l in enumerate(
            l for l, k in enumerate(self.kinds) if k == kind)}
            for kind in _KINDS}
        self.names = tuple(spec.arrays) if spec is not None else ()
        self.n_arrays = len(self.layer["recurrent"]) * len(self.names)
        self.n_window = 2 if window is not None else 0
        self.n_index = 1 if index is not None else 0
        # every counter ``forward`` may add to, and those of them a
        # joint step of ``slots`` rows reads back (all, unless the model
        # says which: a form chosen by the step's shape brings its own)
        self.declared = tuple(getattr(model, "tallies", ()))
        self.tallies = tuple(model.step_tallies(slots)) if hasattr(
            model, "step_tallies") else self.declared
        # what a whole-prompt prefill reads back with its token: its
        # recurrent layers' scans, then those of the model's counters it
        # declares for a prefill
        self.prefill_tallies = (
            _SCAN_TALLIES if self.layer["recurrent"] else ()) + tuple(
            getattr(model, "prefill_tallies", ()))

    def split(self, state):
        n = len(state) - self.n_window - self.n_arrays
        flat, k = state[n + self.n_window:], len(self.names)
        pools = _split_state(state[:n - self.n_index]) \
            + tuple(state[n - self.n_index:n])
        return (pools, tuple(state[n:n + self.n_window]),
                tuple(dict(zip(self.names, flat[i * k:(i + 1) * k]))
                      for i in range(len(self.layer["recurrent"]))))

    def join(self, cache):
        pools, window, rec = cache
        return _join_state(pools) + tuple(window) + tuple(
            layer[name] for layer in rec for name in self.names)


class _Mixers:
    """What ``forward`` of a model with ``layer_kinds`` gets as
    ``attend``: the call is attention as ever, the model's layer mapped
    to ITS kind's pools and layer there (``attend`` for an
    ``"attention"`` layer, ``attend_window`` for a ``"window"`` one, which
    also gets the call's ``sinks``); ``recur(layer, token_fn, rows,
    cache, chunk_fn=None, chunk=0, chunks_per_call=1)`` runs a
    recurrent layer's one-token update ``token_fn(rows, state) -> (out,
    state)`` where the program keeps that state (the joint step hands a
    ``token_fn`` that has a ``live`` parameter the rows' mask and leaves
    the dead rows to it; a whole-prompt prefill
    runs ``chunk_fn(rows, n_real, state)`` over ``chunk`` consecutive
    tokens at once where the model hands one, ``chunks_per_call`` of
    the rule's own chunks a call); ``live`` (bool, the
    rows' shape) says which rows are a request's; ``tally(name, n)``
    adds an int32 scalar to the counter ``name``, one of the model's
    declared ``tallies`` (a joint step's ride its one read-back, in the
    declared order; a name of its ``prefill_tallies`` counts where a
    whole-prompt prefill reads it back and nowhere else);
    ``interpret`` is ``DecodeConfig.interpret`` for a layer's own Pallas
    kernels; ``record(name, rows)`` keeps a per-row array a
    layer for a request that records its logits.  ``prompt`` says the
    rows are ONE prompt's from position 0 and attend themselves alone
    (the whole-prompt prefill): a model whose attention has one form to
    read the cache by and another to attend a prompt in picks by it, and
    hands the call ``keep=``, what the cache keeps of the rows where
    that is not the ``k`` and ``v`` they attend.  ``read_row`` (with
    ``prompt``; an int32 scalar) is the ONE row whose logits the program
    reads, the prompt's last real token: a model may form that row's
    logits alone and hand back ``[1, V]``.  A layer whose attention
    reads the positions an indexer selects hands the call ``index=``
    (``mixers.IndexCall``: the rows' index key for the third pool and
    the indexer's two forms; ``attend_indexed``), and its prompt form
    runs the prompt's causal attention through ``causal(q, k, v, length,
    select=None)``, the form every prompt's takes here."""

    def __init__(self, mixed: _Mixed, recur, live, attend=None,
                 attend_window=None, own_tallies=(), interpret=False,
                 prompt=False, read_row=None, attend_indexed=None,
                 causal=None):
        self._mixed, self._recur, self.attend = mixed, recur, attend
        self.attend_window = attend_window
        self.attend_indexed, self.causal = attend_indexed, causal
        self.live = live
        self.interpret = bool(interpret)
        self.prompt, self.read_row = bool(prompt), read_row
        # ``own_tallies``: counters this program reads back beside the
        # model's declared ones
        self.counts = dict.fromkeys(mixed.tallies + tuple(own_tallies), 0)
        self.records = {}

    def __call__(self, layer, q, k, v, cache, sinks=None, keep=None,
                 index=None):
        pools, window, rec = cache
        if self._mixed.kinds[layer] == "window":
            ctx, window = self.attend_window(
                self._mixed.layer["window"][layer], q, k, v, window, sinks)
        elif index is not None:
            ctx, pools = self.attend_indexed(
                self._mixed.layer["attention"][layer], q, k, v, pools,
                index)
        else:
            ctx, pools = self.attend(
                self._mixed.layer["attention"][layer], q, k, v, pools,
                **({} if keep is None else {"keep": keep}))
        return ctx, (pools, window, rec)

    def recur(self, layer, token_fn, rows, cache, chunk_fn=None, chunk=0,
              chunks_per_call=1):
        pools, window, rec = cache
        i = self._mixed.layer["recurrent"][layer]
        out, new = self._recur(token_fn, rows, rec[i], chunk_fn, chunk,
                               chunks_per_call)
        return out, (pools, window, rec[:i] + (new,) + rec[i + 1:])

    def tally(self, name, value):
        if name in self.counts:
            self.counts[name] += value
        elif name not in self._mixed.declared + self._mixed.prefill_tallies:
            raise KeyError(f"{name!r} is not among the model's declared "
                           f"tallies {self._mixed.declared}")

    def record(self, name, rows):
        self.records.setdefault(name, []).append(rows)

    def recorded(self):
        """{name: [rows, layers, ...]}."""
        import jax.numpy as jnp

        return {n: jnp.stack(v, axis=1) for n, v in self.records.items()}


def _takes_live(token_fn) -> bool:
    """Whether a model's one-token update has a ``live`` parameter: its
    statement that it leaves the state of a row that is not live as it
    was (``DecodeEngine``'s contract)."""
    return "live" in inspect.signature(token_fn).parameters


def layers_of_kind(model, kind: str) -> int:
    """How many of ``model``'s layers are of ``kind`` (one of
    ``"attention"`` | ``"window"`` | ``"recurrent"``); a model that
    declares no ``layer_kinds`` has attention layers only, one a cache
    layer."""
    kinds = getattr(model, "layer_kinds", None)
    if kinds is None:
        return cache_layers(model) if kind == "attention" else 0
    bad = set(kinds) - set(_KINDS)
    if bad:
        raise ValueError(f"layer_kinds holds {sorted(bad)}: a layer is one "
                         f"of {_KINDS}")
    return sum(k == kind for k in kinds)


def recurrent_layers(model) -> int:
    """How many of ``model``'s layers keep state instead of keys."""
    return layers_of_kind(model, "recurrent")


def cache_layers(model) -> int:
    """How many layers of K and V ``model``'s tokens leave in the cache:
    what it declares as ``cache_layers`` (a stack its tokens pass
    through several times on the same weights keeps K and V of every
    pass), else one a weight layer."""
    return int(getattr(model, "cache_layers", model.num_layers))


class _Counting:
    """What ``forward`` of a model WITHOUT ``layer_kinds`` that declares
    ``tallies`` gets as ``attend``: the call as it is, ``live`` (bool,
    the rows' shape: which rows are a request's) and ``tally(name, n)``
    as ``_Mixers`` has them.  ``names`` are the counters this program
    reads back; any other declared one is counted and dropped."""

    def __init__(self, model, names=(), live=True, attend=None):
        self.attend, self.live = attend, live
        self._declared = tuple(model.tallies) + tuple(
            getattr(model, "prefill_tallies", ()))
        self.counts = dict.fromkeys(names, 0)

    @classmethod
    def of(cls, model, attend, names=(), live=True):
        """``attend`` as it is for a model that declares no ``tallies``,
        else behind a ``_Counting`` (``live`` True: every row, where the
        program reads no counter back)."""
        if not getattr(model, "tallies", ()):
            return attend
        return cls(model, names, live, attend)

    def __call__(self, layer, q, k, v, cache):
        return self.attend(layer, q, k, v, cache)

    def tally(self, name, value):
        if name in self.counts:
            self.counts[name] += value
        elif name not in self._declared:
            raise KeyError(f"{name!r} is not among the model's declared "
                           f"tallies {self._declared}")

def _with_counts(tokens, mix, names):
    """``tokens`` (int32 ``[S]``) with ``mix``'s counters ``names``
    behind them: what a joint step's one read-back carries."""
    import jax.numpy as jnp

    return jnp.concatenate([tokens] + [
        jnp.asarray(mix.counts[n], jnp.int32).reshape(1) for n in names])


def per_slot_kinds(model):
    """The kinds of ``model``'s layers whose state is NOT "every
    position in pages" (a recurrent layer's one state a slot, a window
    layer's ring of its last positions), each with what it keeps: what
    every mechanism that replays, skips, shares or exports positions
    has to refuse."""
    keeps = {"recurrent": "keep one state a slot, the state after the "
                          "LAST token",
             "window": "keep a slot's last positions only, in a ring of "
                       "pages that overwrites the oldest"}
    return [(k, keeps[k]) for k in ("recurrent", "window")
            if layers_of_kind(model, k)]


# ---------------------------------------------------------------------------
# requests


class DecodeRequest(RequestBase):
    """Streaming future for one generation request.

    Tokens arrive on an internal stream as the engine produces them:
    iterate ``tokens()`` for a generator, pass ``on_token=`` for a
    callback (called from the engine thread — keep it cheap), or call
    ``result()`` for the completed id list.  ``generated`` always
    holds the ids produced so far (partial output survives a deadline
    reap)."""

    __slots__ = ("prompt", "max_new_tokens", "temperature", "top_k",
                 "top_p", "seed", "on_token", "generated", "_stream",
                 "t_first_token", "t_last_token", "record_logits",
                 "logits_trace", "records", "speculative",
                 "finish_reason", "extract_kv", "kv_import", "kv_export")

    _deadline_stat = "decode_deadline_exceeded"
    _outcome_prefix = "decode"

    def __init__(self, prompt, max_new_tokens, deadline, temperature,
                 top_k, top_p, seed, on_token, record_logits=False,
                 speculative=None, extract_kv=False, kv_import=None):
        super().__init__(deadline)
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.on_token = on_token
        self.generated: List[int] = []
        self._stream: _queue.Queue = _queue.Queue()
        self.t_first_token: Optional[float] = None
        self.t_last_token: Optional[float] = None
        self.record_logits = bool(record_logits)
        self.logits_trace: List[np.ndarray] = []
        # beside the logits, what the model recorded a row (``attend.
        # record``; a routed model's chosen expert ids): {name: [per
        # dispatch, as logits_trace; a prefill's entry holds every
        # prompt position]}
        self.records: dict = {}
        self.speculative = speculative  # None=auto, False=opt out
        self.finish_reason: Optional[str] = None
        # disaggregated serving (serving/disagg.py): an extract_kv
        # request is the INTERNAL prefill leg — on success its slot's
        # prompt pages are gathered into ``kv_export`` (a
        # kv_cache.KVPageExport) before release, and it is exempt from
        # the client-facing SLO plane (ttft histogram + goodput/burn
        # accounting) because the logical request's first token is the
        # decode replica's.  ``kv_import`` carries such a payload INTO
        # an engine: admission installs the pages and starts at the
        # first decode step instead of prefilling.
        self.extract_kv = bool(extract_kv)
        self.kv_import = kv_import
        self.kv_export = None

    # terminal accounting (RequestBase._on_terminal hooks) ---------------
    def _finish_stats(self, outcome, latency):
        # unlike the batcher, decode had NO terminal-latency series at
        # all — record it for EVERY outcome (submit-time rejections
        # observe it separately in DecodeEngine.submit) so error-rate
        # denominators cover deadline/abandon/reject alike
        stat_time("decode_request_latency_seconds", latency)

    def _summary(self, outcome, latency):
        n = len(self.generated)
        ttft = None if self.t_first_token is None \
            else self.t_first_token - self.t_enqueue
        tpot = None
        if n >= 2 and self.t_last_token is not None \
                and self.t_first_token is not None:
            # per-request MEAN time-per-output-token (what the tpot_p50
            # SLO objective judges)
            tpot = (self.t_last_token - self.t_first_token) / (n - 1)
        return {
            "outcome": outcome,
            "latency_s": round(latency, 6),
            "ttft_s": None if ttft is None else round(ttft, 6),
            "tpot_s": None if tpot is None else round(tpot, 6),
            "n_tokens": n,
            "prompt_len": len(self.prompt),
            "reason": self.finish_reason,
        }

    def _slo_check(self, summary):
        if self.extract_kv:
            # internal disagg prefill leg: the logical request is
            # observed once, by its decode-side request — feeding this
            # half too would double-count every disagg request in
            # goodput/burn
            return ()
        from ..observe import slo as _slo

        return _slo.observe_request(summary)

    # engine side ---------------------------------------------------------
    def _emit(self, token: int) -> None:
        now = time.monotonic()
        if self.t_first_token is None:
            self.t_first_token = now
            if not self.extract_kv:
                stat_time("ttft_seconds",
                          self.t_first_token - self.t_enqueue)
        self.t_last_token = now
        self.generated.append(int(token))
        self._stream.put(int(token))
        if self.on_token is not None:
            try:
                self.on_token(int(token))
            except Exception:  # noqa: BLE001 — user callback, isolate
                stat_add("decode_callback_errors")

    def _finish(self, error=None) -> bool:
        won = self._complete(result=list(self.generated), error=error)
        self._stream.put(_DONE)  # always: a racing client-side reap
        # must still terminate a tokens() reader
        return won

    # client side ---------------------------------------------------------
    def tokens(self, timeout: Optional[float] = None):
        """Generator over streamed token ids; raises the request's
        error (after yielding everything produced) if it failed."""
        while True:
            budget = timeout
            if self.deadline is not None:
                # the engine reaps at the next step boundary; the small
                # grace covers its in-flight step
                rem = max(self.deadline - time.monotonic(), 0.0) + 1.0
                budget = rem if budget is None else min(budget, rem)
            try:
                item = self._stream.get(timeout=budget)
            except _queue.Empty:
                raise TimeoutError(
                    "no token within the wait budget") from None
            if item is _DONE:
                break
            yield item
        if self._error is not None:
            raise self._error


class _SlotState:
    __slots__ = ("req", "base_key", "n_generated", "last_token", "t_last",
                 "phase", "prefill_pos", "write_trash_once", "spec",
                 "draft_lag", "chunks", "t_admit", "ahead")

    def __init__(self, req, base_key):
        self.req = req
        self.base_key = base_key
        self.n_generated = 0
        # joint steps handed over for this slot whose tokens are not on
        # the host yet (0 or 1): the next step's position and sampling
        # counter are that much further on
        self.ahead = 0
        self.last_token = 0
        self.t_last = time.monotonic()
        self.t_admit = self.t_last
        self.chunks = 0             # prefill chunks dispatched
        self.phase = "prefill"      # "prefill" -> "decode"
        self.prefill_pos = 0        # next prompt position to prefill
        self.write_trash_once = False  # cache-hit path: first decode
        # write re-derives a position the shared pages already hold
        self.spec = False           # speculative-decode eligible
        self.draft_lag = 0          # trailing positions written by the
        # normal step (target-only) on a spec slot — the draft pool is
        # stale there, so registration excludes them


# ---------------------------------------------------------------------------
# engine


class DecodeConfig:
    """Engine knobs; defaults come from the ``FLAGS_decode_*`` flags."""

    def __init__(self, slots: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 max_queue: int = 256,
                 default_deadline_ms: Optional[float] = None,
                 use_pallas: str = "auto",
                 interpret: bool = False,
                 cache_dtype="float32",
                 prefix_cache: Optional[bool] = None,
                 prefill_chunk_pages: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 kv_quant: Optional[bool] = None):
        from ..framework import flags

        self.slots = int(slots if slots is not None
                         else flags.flag("decode_slots"))
        self.max_seq_len = int(max_seq_len if max_seq_len is not None
                               else flags.flag("decode_max_seq_len"))
        self.page_size = int(page_size if page_size is not None
                             else flags.flag("decode_page_size"))
        self.num_pages = num_pages
        self.max_new_tokens = int(
            max_new_tokens if max_new_tokens is not None
            else flags.flag("decode_max_new_tokens"))
        self.eos_id = eos_id
        self.max_queue = int(max_queue)
        self.default_deadline_ms = default_deadline_ms
        self.use_pallas = use_pallas
        self.interpret = bool(interpret)
        self.cache_dtype = cache_dtype
        self.prefix_cache = bool(
            prefix_cache if prefix_cache is not None
            else flags.flag("decode_prefix_cache"))
        self.prefill_chunk_pages = int(
            prefill_chunk_pages if prefill_chunk_pages is not None
            else flags.flag("decode_prefill_chunk_pages"))
        self.spec_k = int(spec_k if spec_k is not None
                          else flags.flag("decode_spec_k"))
        self.kv_quant = bool(kv_quant if kv_quant is not None
                             else flags.flag("decode_kv_quant"))


class DecodeEngine:
    """One decode replica: a slot batch, its paged KV cache, and the
    consumer thread that runs admission -> prefill -> joint decode
    step, forever.

    What the engine reads off ``model`` is the whole contract
    (``serving/transformer_lm.py`` is the reference): ``num_layers``,
    ``num_heads``, ``head_dim``, ``vocab_size``, ``max_seq_len`` and
    ``forward(weights, tokens, positions, cache, attend) -> (logits,
    cache)`` for any leading shape of ``tokens``, which calls
    ``attend(layer, q, k, v, cache) -> (ctx, cache)`` once a CACHE
    layer with ``[..., H, D]`` rows.  A model may declare
    ``cache_layers`` (default ``num_layers``): how many layers of K and
    V a token leaves behind, which is what sizes, counts and moves the
    pools (their depth, a page's bytes and the budget, the draft's
    pools, the hand-over).  ``attend``'s first argument is the cache
    layer the call writes and reads: a Python ``int``, or a traced
    ``int32`` scalar where ``forward`` runs its layers in a rolled loop
    (``serving/looped_lm.py``: a stack every token passes through
    ``loops`` times on the same weights, pass ``t`` of layer ``l`` at
    cache layer ``t * num_layers + l``); every cache layer is written
    exactly once a call of ``forward``.  ``weights`` is the caller's
    pytree (``init_weights`` is for callers).  The engine supplies
    ``attend`` — where K/V reach the pools and what is attended: one
    token a slot, a whole prompt, R rows a slot — and nothing else
    about the model; bitwise parity of cached decode with a recompute
    needs a ``forward`` whose other operations are row-independent.

    **Three kinds of layer, geometry by kind, two lifetimes**
    (``serving/hybrid_moe_lm.py`` and ``serving/window_moe_lm.py`` are
    the references).  A model may also declare ``num_kv_heads``
    (grouped-query heads: ``k``/``v`` rows of ``[..., Hkv, D]``, the
    pools ``Hkv * D`` lanes wide, query head i reading K/V head ``i //
    (H / Hkv)``), ``v_head_dim`` (V heads narrower than K's ``head_dim``:
    the V pool's rows, and ``ctx``, have that width) and ``layer_kinds``,
    one of ``"attention"`` | ``"window"`` | ``"recurrent"`` a layer:

    * ``"attention"``: every position in pages, for as long as the
      request lives (the pools above; they hold these layers only).
    * ``"window"``: the layer attends a position's last ``window``
      positions (itself counted) and, where its call hands ``sinks=``
      (a logit a query head), the sink in the softmax's denominator.
      The model declares ``window`` and ``window_kv_heads`` (this
      kind's own K/V head count); its K/V live in a second pair of
      pools in which a slot owns a RING of ``ceil(window / page) + 1``
      pages a layer, whatever the request's length
      (``kv_cache.WindowSpec``): the step writes the token into the
      ring and attends the ring, the whole-prompt prefill attends the
      prompt's bucket masked to the window and leaves only the window's
      tail in the ring.  Nothing is uploaded for it.
    * ``"recurrent"``: ``recurrent_state``, ``{name: (shape, dtype)}``
      of ONE slot's state of ONE such layer, in slot-indexed slabs
      (``kv_cache.RecurrentSpec``).  The state may be a convolution's
      last inputs alone, and the ``chunk`` of its prompt form the whole
      bucket (``serving/conv_moe_lm.py``: a layer that reads no state
      but its own inputs runs a prompt in one call).

    ``tallies`` are the names of the counters ``forward`` adds to (a
    model without ``layer_kinds`` that declares some gets ``attend``
    with ``live`` and ``tally`` as below, a ``_Counting``; the multi-row
    step and a draft's burst count and drop them); a
    model with ``step_tallies(rows)`` says which of them a joint step
    of ``rows`` rows reads back (a form of a layer chosen by the step's
    shape brings its counters: the others are counted and dropped).
    ``attend`` is then a ``_Mixers``: the call as above for an attention
    or window layer (mapped to ITS kind's pools);
    ``attend.recur(layer, token_fn, rows, cache, chunk_fn=None, chunk=0,
    chunks_per_call=1) -> (out, cache)`` for a recurrent one, where
    ``token_fn(rows, state)
    -> (out, state)`` is the model's one-token update over rows
    ``[R, ...]`` and the engine decides what state that is and where it
    goes (the joint step: every live slot's row, in place; the
    whole-prompt prefill: from zero through the prompt's real tokens,
    the last one's state into the slot's row).  In the joint step a
    dead slot's row has to stay as it is, and whose work that is the
    update's signature says: a ``token_fn`` WITH a ``live`` parameter
    is called ``token_fn(rows, state, live=live)`` (bool ``[R]``) and
    promises to hand a row that is not live back with the state it had,
    every array of it, bit for bit; the engine then touches no slab
    itself (an update that is a kernel over the slabs in place stays the
    only instruction that passes over them).  One without it is called
    ``token_fn(rows, state)`` and the engine masks what it returns
    (``where(live, new, old)``, a pass over every slab).  A prefill
    passes no ``live``: its rows are one request's.  The prefill takes the
    tokens one by one through ``token_fn`` unless the model also hands
    ``chunk_fn(rows, n_real, state) -> (out, state)``: the same rule over
    ``chunk`` consecutive rows of ONE request from the state before them
    (leading dimension 1), of which only the first ``n_real`` are the
    request's and may touch the state; it then runs once a chunk, in
    ONE loop a layer that carries the state and holds all of the call.
    ``chunk`` is what ONE CALL covers; a model whose rule has a chunk of
    its own and whose call takes a group of them says how many as
    ``chunks_per_call`` (``serving/gated_delta_lm.py``: what does not
    read the state formed for the whole group as XLA operations, the
    state passed through its chunks in turn; ``serving/mixers.py``
    ``KDAMixer``: the group's vectors formed at once and ONE kernel call
    that keeps the state in fast memory through the group's chunks),
    and declares ``prefill_chunks_per_call(rows)``,
    the same number for a prompt bucket of ``rows`` (0: it hands no
    chunk form; gauge ``decode_prefill_chunks_per_call``, at the largest
    bucket).  Counters ``decode_prefill_scan_steps`` /
    ``decode_prefill_scan_tokens``: the rule's chunks that held a real
    token (calls, where a call is one chunk) and real tokens, a
    recurrent layer.
    ``attend.live``
    (which rows are a request's), ``attend.tally(name, n)`` (counters
    the model declares by name in ``tallies``; they ride the step's one
    read-back into ``stat_add(name)``; those it names in
    ``prefill_tallies`` ride a whole-prompt prefill's token instead and
    are not counted in a step), ``attend.interpret``
    (``DecodeConfig.interpret``, for a layer's own Pallas kernels) and
    ``attend.record(name, rows)``
    (a per-row array a layer, kept beside the logits of a
    ``record_logits`` request in ``req.records``).  A model with window
    or recurrent layers is served by the whole-prompt prefill and the
    joint step alone: every request is admitted fresh (no prefix index;
    counter ``decode_prefix_bypassed``), and chunked prefill,
    speculative decoding, ``kv_quant`` and the disaggregated hand-over
    refuse at construction or submit, naming the kind and the mechanism
    (``per_slot_kinds``).

    **One row for keys and values** (``serving/latent_moe_lm.py`` is
    the reference).  A model with latent attention declares
    ``values_in_keys`` beside ``num_kv_heads`` 1: what it caches of a
    position is ONE row of ``head_dim`` lanes that all its query heads
    read, whose first ``v_head_dim`` lanes are the values.  Its layers
    are ``"attention"`` layers (every position in pages, the same free
    list, tables and admission); the cache has the K pool alone
    (``kv_cache.CacheConfig(latent=True)``).  In the step it hands
    ``attend`` the query in the row's space, the row as ``k`` and ``v``
    None; in the whole-prompt prefill (``attend.prompt``) whatever heads
    it attends the prompt in (``prompt_heads``: their K/V head count, K
    and V lanes) and the rows to cache as ``keep=``, and forms the
    logits of ``attend.read_row`` alone.  Every request is
    admitted fresh (``decode_prefix_bypassed``), and chunked
    prefill, speculation, ``kv_quant`` and the hand-over refuse, naming
    the latent page: the programs that extend a sequence R rows at a time
    through the pages are not built for its rows.

    **A third pool of index keys** (``serving/indexed_moe_lm.py`` is the
    reference).  A model whose attention layers read only the positions
    a learned indexer selects declares ``index_dim`` (lanes of the one
    index key a position a layer keeps beside K and V) and
    ``index_topk``; the cache then has a third pool behind the K/V
    pools' page ids (``kv_cache.IndexSpec``: one table, one free list).
    Its layers are ``"attention"`` layers and hand every call
    ``index=`` (``mixers.IndexCall``).  The step writes the token's
    key, hands the indexer each slot's cached keys with the slot's
    length (it masks before it selects: a recycled page's stale rows are
    positions past the length) and attends the rows at the positions it
    gets back, gathered from the pools; the whole-prompt prefill writes
    the prompt's keys and hands the indexer the prompt's own rows.
    Every request is admitted fresh (``decode_prefix_bypassed``), and
    chunked prefill, speculation, ``kv_quant`` and the hand-over refuse,
    naming the index pool.  Counters ``decode_index_positions_scored`` /
    ``decode_index_positions_selected`` (a layer's, over the live slots
    of a step), gauge ``decode_index_bytes``.

    ``draft_model``/``draft_weights`` arm speculative decoding (with
    ``spec_k > 0``): the draft's page pools are indexed by the SAME
    page ids as the target's, so prefix sharing, reservation
    accounting, and copy-on-write cover both for free."""

    def __init__(self, model, weights, config: Optional[DecodeConfig] = None,
                 place=None, name: str = "replica-0",
                 draft_model=None, draft_weights=None):
        import jax
        import jax.numpy as jnp

        from ..framework.executor import Executor
        from ..framework.scope import Scope

        self.model = model
        self.config = config or DecodeConfig()
        self.name = name
        c = self.config
        if c.max_seq_len > model.max_seq_len:
            raise ValueError(
                f"DecodeConfig.max_seq_len {c.max_seq_len} exceeds the "
                f"model's positional table ({model.max_seq_len})")
        self._draft_model = draft_model
        if draft_model is not None:
            if draft_weights is None:
                raise ValueError(
                    "draft_model needs draft_weights for speculative "
                    "decoding")
            if int(draft_model.vocab_size) != int(model.vocab_size):
                raise ValueError(
                    f"speculative draft/target vocab mismatch: draft "
                    f"{draft_model.vocab_size} vs target "
                    f"{model.vocab_size} — the draft's proposals would "
                    f"index a different token space; re-export the "
                    f"draft with the target's vocabulary")
            if int(draft_model.max_seq_len) < c.max_seq_len:
                raise ValueError(
                    f"draft positional table ({draft_model.max_seq_len})"
                    f" is shorter than max_seq_len ({c.max_seq_len})")
        self._scope = Scope()
        self._exe = Executor(place)
        # an explicit ``place`` PINS this replica: weights and page
        # pools are committed to its device, and every jitted step
        # follows its committed operands there (DecodeServer/
        # DisaggServer hand replica i local device i mod n).  place=None
        # leaves everything uncommitted on the default device, which is
        # also what mesh-sharded (expert-parallel) weights need
        self._device = self._exe.place.jax_device()
        self._pinned = place is not None
        # layers that keep state instead of keys, or a window of their
        # keys: pools for the attention layers only, slot-indexed slabs
        # and rings for the others
        n_rec = recurrent_layers(model)
        n_win = layers_of_kind(model, "window")
        spec = RecurrentSpec(n_rec, model.recurrent_state) if n_rec \
            else None
        kv_heads = getattr(model, "num_kv_heads", model.num_heads)
        v_dim = getattr(model, "v_head_dim", model.head_dim)
        self._window = WindowSpec(
            n_win, getattr(model, "window_kv_heads", kv_heads),
            model.head_dim, v_dim, model.window) if n_win else None
        # ... and the index keys of layers that select what they attend:
        # a third pool behind the same page ids
        n_idx = layers_of_kind(model, "attention") \
            if getattr(model, "index_dim", 0) else 0
        self._index = IndexSpec(n_idx, model.index_dim) if n_idx else None
        self._mixed = _Mixed(model, spec, self._window, c.slots,
                             self._index) \
            if getattr(model, "layer_kinds", None) else None
        # the model's counters behind a step's tokens, as it declares them
        self._tallies = self._mixed.tallies if self._mixed \
            else tuple(getattr(model, "tallies", ()))
        # and what rides a whole-prompt prefill's token
        self._prefill_tallies = self._mixed.prefill_tallies \
            if self._mixed else tuple(getattr(model, "prefill_tallies", ()))
        latent = bool(getattr(model, "values_in_keys", False))
        self._refuse(model, c, draft_model, latent,
                     indexed=self._index is not None)
        with jax.default_device(self._device):
            self._cache = PagedKVCache(
                CacheConfig(max(cache_layers(model) - n_rec - n_win, 1),
                            kv_heads, model.head_dim, c.slots,
                            c.max_seq_len, c.page_size,
                            num_pages=c.num_pages, dtype=c.cache_dtype,
                            quantized=c.kv_quant, v_head_dim=v_dim,
                            latent=latent),
                self._scope, prefix_cache=c.prefix_cache, recurrent=spec,
                window=self._window, index=self._index)
        # whether the pools' one layout is also an unpadded one (the
        # tile rule in serving/kv_cache.py): the counter that says the
        # lane-dense representation engaged for this model's shape
        stat_set("decode_kv_lane_dense",
                 1 if self._cache.config.lane_dense else 0)
        stat_set("decode_kv_pool_row_lanes", self._cache.config.row_lanes)
        # ... and whether a position's K and V share one pool row (one
        # K/V head of whole lane tiles: ``CacheConfig.joint``)
        stat_set("decode_kv_joint_rows", 1 if self._cache.config.joint else 0)
        # positions one block of the paged-attention kernel covers at
        # this shape (the op reads the same rule from the same shapes),
        # and the blocks of all the slots' tables
        from ..ops.pallas_decode_attention import pages_per_block

        cc = self._cache.config
        k_lanes, v_lanes = cc.attended_lanes()
        self._attn_block = cc.page_size * pages_per_block(
            cc.page_size, cc.pages_per_slot, k_lanes, cc.store_dtype,
            v_lanes, kv_heads, model.num_heads // kv_heads)
        stat_set("decode_attn_block_positions", self._attn_block)
        # the rule's own chunks ONE call of the model's chunk form takes
        # of a recurrent layer's prompt, at the largest bucket (0: the
        # model hands no chunk form)
        stat_set("decode_prefill_chunks_per_call", getattr(
            model, "prefill_chunks_per_call", lambda rows: 0)(
                c.max_seq_len))
        self._attn_table_blocks = cc.num_slots * -(
            -cc.max_seq_len // self._attn_block)
        if self._window is not None:
            w = self._window
            self._ring = w.ring_pages(cc.page_size)
            self._window_block = cc.page_size * pages_per_block(
                cc.page_size, self._ring, w.num_heads * w.head_dim,
                cc.store_dtype, w.num_heads * w.v_head_dim, w.num_heads,
                model.num_heads // w.num_heads)
            stat_set("decode_window_block_positions", self._window_block)
        # per-request timeline hook: claim/CoW/register/evict events
        # from the cache land on the owning request's trace
        self._cache.on_event = self._on_cache_event
        self._admitting = None  # request whose claim() is in flight
        self.weights = self._commit(weights)
        # persistent-state tuples every jitted step threads (the scale
        # pools join them under FLAGS_decode_kv_quant)
        self._state_vars = self._cache.state_var_names()
        n_pools = len(self._state_vars) \
            - len(self._cache.index_var_names()) \
            - len(self._cache.window_var_names()) \
            - len(self._cache.recurrent_var_names())
        self._draft_state_vars = ()
        if draft_model is not None:
            self.draft_weights = self._commit(draft_weights)
            cc = self._cache.config
            dshape = cc.pool_shape(
                cache_layers(draft_model),
                draft_model.num_heads * draft_model.head_dim)
            self._scope.set_var(DRAFT_K_PAGES_VAR,
                                jnp.zeros(dshape, cc.store_dtype))
            self._scope.set_var(DRAFT_V_PAGES_VAR,
                                jnp.zeros(dshape, cc.store_dtype))
            self._draft_state_vars = _DRAFT_VARS
            if cc.quantized:
                dsshape = cc.pool_shape(cache_layers(draft_model),
                                        draft_model.num_heads)
                for nm in (DRAFT_K_SCALES_VAR, DRAFT_V_SCALES_VAR):
                    self._scope.set_var(
                        nm, jnp.full(dsshape, kv_cache.SCALE_EPS,
                                     cc.scale_dtype))
                self._draft_state_vars = _DRAFT_VARS + (
                    DRAFT_K_SCALES_VAR, DRAFT_V_SCALES_VAR)
                # freed-page scale resets + the debug_check audit must
                # cover the draft pools too (same page ids)
                self._cache.scale_vars += [DRAFT_K_SCALES_VAR,
                                           DRAFT_V_SCALES_VAR]
        for nm in self._state_vars + self._draft_state_vars:
            self._scope.set_var(nm, self._commit(self._scope.get_var(nm)))
        self._buckets = BucketSpec(
            (1,), prefill_bucket_grid(c.max_seq_len, c.page_size))
        self._step_row = _step_row(self._cache.config.pages_per_slot)
        self._step_fn = self._build_step_fn(model)
        self._prefill_fns = {}   # (t_pad, which, qz) -> jitted prefill
        self._rows_fns = {}      # (rows, slots, which) -> jitted multirow
        self._propose_fn = None  # draft k-token burst (lazy)
        self._cow_fn = None      # page copy across every pool (lazy)
        self._cow_state = self._state_vars[:n_pools] \
            + self._draft_state_vars
        self._slots: List[Optional[_SlotState]] = [None] * c.slots
        self._queue = collections.deque()
        self._cond = threading.Condition()
        self._closing = False
        self._abort = False
        self._thread = None
        self._seq = 0  # default-seed counter
        self._prefill_rr = 0  # chunked-prefill round-robin cursor
        self.tokens_total = 0
        # per-replica tentpole accounting (stats()/DecodeServer /stats)
        self._hit_pages = 0
        self._prompt_pages = 0
        self._cow_copies = 0
        self._prefill_chunk_count = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        # decode rounds handed over by THIS engine (joint steps and
        # speculative rounds): the step spans' ``step``
        self._decode_steps = 0
        # the joint step handed over and not yet read (the half of
        # ``_dispatch_step`` that reads and delivers it), and the tokens
        # of the last joint step as they lie on the device: what the next
        # step's ``_CARRY`` rows read.  Zeros until a step has run and
        # after one has failed
        self._flying = None
        self._no_tokens = self._where_tokens_land(np.zeros(
            c.slots + len(self._tallies), np.int32))
        self._step_tokens = self._no_tokens
        # ``_loop`` iterations begun: the leaf spans' ``iter``
        self._iter = 0
        # what the open ``*_deliver`` span carried so far (tokens,
        # slots that ended, ns in ``req._emit``, ns in ``_finish_slot``),
        # None while no sink takes it or none is open: ``_Delivery``
        self._carried = None
        # when the last joint step's tokens reached the host (None
        # after an idle wait): where ``decode_turnaround_seconds`` starts
        self._t_tokens = None
        # whether the joint step's program has run once: its first run
        # (a compile, or a load from the compile cache) is no slow step
        self._step_ran = False

    @classmethod
    def _refuse(cls, model, c: "DecodeConfig", draft_model, latent,
                indexed=False) -> None:
        """What the configuration asks for that the model's layers or
        its page cannot carry.  A model may keep state a slot in some
        layers AND a latent page in the others: the refusal then names
        both, each with its own reason."""
        refusals = [(cls._refuse_for_kinds, (model, c, draft_model))]
        if latent:
            refusals.append((cls._refuse_for_latent, (c, draft_model)))
        if indexed:
            refusals.append((cls._refuse_for_index, (c, draft_model)))
        said = []
        for refuse, args in refusals:
            try:
                refuse(*args)
            except ValueError as e:
                said.append(str(e))
        if said:
            raise ValueError("; and ".join(said))

    @staticmethod
    def _refuse_for_kinds(model, c: "DecodeConfig", draft_model) -> None:
        """A model with layers whose state is not "every position in
        pages" (``per_slot_kinds``) runs the whole-prompt prefill and
        the joint step; every mechanism that replays, skips or exports
        positions would need a state, or a position, that nothing
        holds, and refuses here, by kind and mechanism, rather than run
        wrong."""
        for kind, keeps in per_slot_kinds(model):
            why = f"the model's {kind} layers {keeps}: "
            if c.prefill_chunk_pages > 0:
                raise ValueError(
                    why + "chunked prefill (prefill_chunk_pages="
                    f"{c.prefill_chunk_pages}) would have to carry that "
                    "from chunk to chunk, which the multi-row step does "
                    "not")
            if draft_model is not None or c.spec_k > 0:
                raise ValueError(
                    why + "speculative decoding (a draft model, spec_k="
                    f"{c.spec_k}) would have to rewind that past rejected "
                    "proposals, which nothing here can")
            if c.kv_quant:
                raise ValueError(
                    why + "kv_quant (int8 K/V pages) is not wired for a "
                    "model whose pools hold only some of its layers")

    @staticmethod
    def _refuse_for_latent(c: "DecodeConfig", draft_model) -> None:
        """A model that caches one latent row a position is served by
        the whole-prompt prefill and the joint step: what extends a
        sequence R rows at a time THROUGH the pages would have to run
        its attention in the row's space at R times its heads in
        stacked rows, and an int8 row has no head to scale by."""
        why = ("the model keeps a latent page (one row a position for "
               "keys and values): ")
        if c.prefill_chunk_pages > 0:
            raise ValueError(
                why + "chunked prefill (prefill_chunk_pages="
                f"{c.prefill_chunk_pages}) attends its rows through the "
                "pages, which the multi-row step is not built to do in "
                "the row's space")
        if draft_model is not None or c.spec_k > 0:
            raise ValueError(
                why + "speculative decoding (a draft model, spec_k="
                f"{c.spec_k}) verifies its window through the pages with "
                "the multi-row step, which is not built for such rows")
        if c.kv_quant:
            raise ValueError(
                why + "kv_quant (int8 K/V pages) scales a row a head, "
                "and the latent row has none")

    @staticmethod
    def _refuse_for_index(c: "DecodeConfig", draft_model) -> None:
        """A model that keeps an index pool is served by the
        whole-prompt prefill and the joint step: what extends a
        sequence R rows at a time THROUGH the pages would have to score
        and select for each of its rows against the pool, and an int8
        page would be gathered row by row with its scales."""
        why = ("the model keeps an index pool (a third array a position: "
               "the keys its attention selects by): ")
        if c.prefill_chunk_pages > 0:
            raise ValueError(
                why + "chunked prefill (prefill_chunk_pages="
                f"{c.prefill_chunk_pages}) attends its rows through the "
                "pages, where the multi-row step neither scores the "
                "cached keys nor selects")
        if draft_model is not None or c.spec_k > 0:
            raise ValueError(
                why + "speculative decoding (a draft model, spec_k="
                f"{c.spec_k}) verifies its window through the pages with "
                "the multi-row step, which selects nothing")
        if c.kv_quant:
            raise ValueError(
                why + "kv_quant (int8 K/V pages) is not wired for rows "
                "gathered by position, and the index keys are scored as "
                "they lie")

    def _commit(self, tree):
        """Device arrays for ``tree``; on a pinned replica every leaf
        that is not already spread over a mesh is committed to the
        replica's device."""
        import jax
        import jax.numpy as jnp

        def leaf(x):
            if self._pinned and not (
                    isinstance(x, jax.Array)
                    and len(x.sharding.device_set) > 1):
                return jax.device_put(x, self._device)
            return jnp.asarray(x)

        return jax.tree_util.tree_map(leaf, tree)

    def _where_tokens_land(self, tokens):
        """``tokens`` placed as the step's own output is: replicated over
        the mesh the weights are spread over, else as ``_commit`` places
        a leaf.  The step's first run then takes the operand its later
        runs take, and compiles once."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        for leaf in jax.tree_util.tree_leaves(self.weights):
            spread = getattr(leaf, "sharding", None)
            if isinstance(spread, NamedSharding) \
                    and len(spread.device_set) > 1:
                return jax.device_put(
                    tokens, NamedSharding(spread.mesh, PartitionSpec()))
        return self._commit(tokens)

    @property
    def device(self):
        """The jax device this replica's weights and page pools live on."""
        return self._device

    @property
    def spec_enabled(self) -> bool:
        return self._draft_model is not None and self.config.spec_k > 0

    # -- per-request tracing helpers -------------------------------------
    @staticmethod
    def _tev(req, name, **attrs) -> None:
        tr = req.trace
        if tr is not None:
            tr.event(name, **attrs)

    def _on_cache_event(self, slot, name, **attrs):
        """PagedKVCache event hook: attribute cache lifecycle events
        (claim / cow_swap / evict / register) to the owning request's
        timeline.  During admission the slot state does not exist yet,
        so the claim-in-flight request is the fallback owner (evictions
        triggered by its allocation ARE its wait)."""
        st = self._slots[slot] if slot is not None \
            and 0 <= slot < len(self._slots) else None
        req = st.req if st is not None else self._admitting
        if req is not None:
            self._tev(req, f"cache/{name}",
                      **({"slot": slot} if slot is not None else {}),
                      **attrs)

    # -- jitted step builders --------------------------------------------
    def _paged_attend(self, attention, page_table, lengths, write_page,
                      write_off):
        """The ``attend`` of the programs that extend sequences THROUGH
        the pools: a layer's K/V rows written at explicit (page, offset)
        coords, then each query row attending its slot's page table up
        to its own length.  ``attention``: ``paged_decode_attention``
        for ``[S]`` rows (the token step), ``paged_chunk_attention`` for
        ``[S, R]`` (the multi-row step).  Quantized pools (scales not
        None) write int8 + scales; attention dequantizes inline."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas_decode_attention import KERNEL_NAME

        cc = self._cache.config

        def attend(l, q, k, v, pools):
            k_pages, v_pages, k_scales, v_scales = pools
            # a joint cache's one pool (``v_pages`` None, as a latent
            # one's; the draft model's pools are always two): a row is
            # the head's keys, then its values, landed by ONE scatter
            joint = v_pages is None and cc.joint
            if joint:
                k = jnp.concatenate([k, v], axis=-1)
            flat = (-1,) + k.shape[-2:]                     # [rows, H, D]
            k_pages, k_scales = kv_cache.write_token_layer(
                k_pages, k_scales, l, k.reshape(flat),
                write_page.reshape(-1), write_off.reshape(-1))
            if v_pages is not None:
                v_pages, v_scales = kv_cache.write_token_layer(
                    v_pages, v_scales, l, v.reshape((-1,) + v.shape[-2:]),
                    write_page.reshape(-1), write_off.reshape(-1))
            # all backend dispatch (auto/always/never, Pallas vs the
            # gather+mask reference) lives in ONE place: the op itself —
            # including the quantized dequant-inline paths.  The scope
            # is metadata only: it names the call's device ops in a trace
            # one pool: the values are lanes of the rows just written,
            # a latent row's leading ones, a joint row's after the keys
            with jax.named_scope(
                    KERNEL_NAME if v_pages is not None or joint
                    else LATENT_SCOPE):
                ctx = attention(
                    q, k_pages, v_pages, page_table, lengths, layer=l,
                    use_pallas=self.config.use_pallas,
                    interpret=self.config.interpret,
                    k_scales=k_scales, v_scales=v_scales,
                    value_lanes=None if v_pages is not None
                    else cc.v_head_dim,
                    value_offset=cc.head_dim if joint else None)
            return ctx, (k_pages, v_pages, k_scales, v_scales)

        return attend

    def _window_attend(self, lengths, write_page, write_off):
        """The joint step's ``attend`` of a window layer: the token's
        K/V written into the slot's ring at (page, offset), then each
        row attending the ring's last ``window`` positions (and the
        layer's sink); the ring's table is a constant of the program."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas_decode_attention import paged_decode_attention

        cc, w = self._cache.config, self._window
        table = jnp.asarray(w.ring_table(cc.num_slots, cc.page_size))

        def attend(l, q, k, v, pools, sinks):
            k_pages, v_pages = pools
            k_pages = kv_cache.scatter_token_layer(
                k_pages, l, k, write_page, write_off)
            v_pages = kv_cache.scatter_token_layer(
                v_pages, l, v, write_page, write_off)
            with jax.named_scope(WINDOW_SCOPE):
                ctx = paged_decode_attention(
                    q, k_pages, v_pages, table, lengths, layer=l,
                    use_pallas=self.config.use_pallas,
                    interpret=self.config.interpret, window=w.window,
                    sinks=sinks)
            return ctx, (k_pages, v_pages)

        return attend

    def _indexed_attend(self, page_table, lengths, write_page, write_off):
        """The joint step's ``attend`` of a layer that selects what it
        attends: the token's K, V and index key written at (page,
        offset); each slot's cached keys, gathered by its table, handed
        to the indexer with the slot's length and every table position's
        flat row; the rows it selects gathered from the K and V pools and
        attended.  Every gather reads the WHOLE pool by a flat row (a
        layer's slice of a pool is a copy of it)."""
        import jax
        import jax.numpy as jnp

        from ..ops.indexed_attention import attend_rows
        from .mixers import SPARSE_ATTN_SCOPE

        cc = self._cache.config
        kv_heads = cc.num_heads
        # a table position's row inside ONE layer of a pool
        within = (page_table[:, :, None] * cc.page_size + jnp.arange(
            cc.page_size, dtype=jnp.int32)).reshape(page_table.shape[0], -1)

        def attend(l, q, k, v, pools, index):
            k_pages, v_pages, k_scales, v_scales, i_pages = pools
            flat = (-1,) + k.shape[-2:]
            k_pages = kv_cache.scatter_token_layer(
                k_pages, l, k.reshape(flat), write_page, write_off)
            v_pages = kv_cache.scatter_token_layer(
                v_pages, l, v.reshape((-1,) + v.shape[-2:]), write_page,
                write_off)
            i_pages = kv_cache.scatter_token_layer(
                i_pages, l, index.key, write_page, write_off)
            n_pages, page = i_pages.shape[1:3]
            # every index is in bounds (a dead table entry is page 0):
            # "clip" spares the pass that would fill what is not
            keys = jnp.take(
                i_pages.reshape((-1,) + i_pages.shape[2:]),
                l * n_pages + page_table, axis=0,
                mode="clip")                            # [S, P, page, lanes]
            # where each table position's K and V lie, as a flat row of
            # the pools: it rides the selection's sort (a gather of the
            # table by the selected positions would cost as much)
            _, ok, at = index.step(
                keys.reshape(keys.shape[0], -1, keys.shape[-1]), lengths,
                l * n_pages * page + within)
            with jax.named_scope(SPARSE_ATTN_SCOPE):
                rows = [jnp.take(p.reshape(-1, p.shape[-1]), at, axis=0,
                                 mode="clip")
                        for p in (k_pages, v_pages)]
                ctx = attend_rows(q, *rows, ok, kv_heads)
            return ctx, (k_pages, v_pages, k_scales, v_scales, i_pages)

        return attend

    def _token_step_body(self, model, weights, pools, tokens, positions,
                         page_table, write_page, write_off, mix=None):
        """One single-token step of ``model`` over ``pools``, the token
        written THIS step attended with the slot's history.  Shared
        VERBATIM by the target step and the draft proposal burst so both
        read the cache through one formulation.  -> (logits, pools).
        ``mix`` (a ``_Mixers``) takes the attention for a model with
        ``layer_kinds`` (``pools`` is then its ``(pools, recurrent)``),
        or (a ``_Counting``) hands it on for one that only counts."""
        from ..ops.pallas_decode_attention import paged_decode_attention

        attend = self._paged_attend(
            paged_decode_attention, page_table, positions + 1,
            write_page, write_off)
        if mix is not None:
            mix.attend, attend = attend, mix
        else:
            # a draft's burst: its counters are counted and dropped
            attend = _Counting.of(model, attend)
        return model.forward(weights, tokens, positions, pools, attend)

    def _build_step_fn(self, model):
        import jax
        import jax.numpy as jnp

        from ..ops.sampling_ops import sample_tokens

        # the scopes on the jitted bodies are metadata only: a trace's
        # device ops read "jit(step)/decode_step/...".  The functions
        # keep their names (the programs are jit_step, jit_prefill, ...)
        row = self._step_row
        page_size = self._cache.config.page_size
        mixed = self._mixed

        eos = self.config.eos_id

        @jax.named_scope("decode_step")
        def step(state, weights, packed, carried):
            a = _unpack(packed, row)                        # fields [S]
            positions, page_table = a["position"], a["pages"]
            live = (a["flags"] & _LIVE) != 0
            # a slot that was live in the joint step in flight takes its
            # token from that step's output, which never visits the
            # host (``carried``: the tokens, then that step's tallies)
            carry = (a["flags"] & _CARRY) != 0
            tokens = jnp.where(carry, carried[:carry.shape[0]], a["token"])
            if eos is not None:
                # the host has not seen that token: a row whose carried
                # token ends its request runs dead (its write aims at
                # the trash page, its state rows and ring stay), and
                # the delivery drops what it yields
                live = live & ~(carry & (tokens == eos))

            def recur(token_fn, rows, rec, chunk_fn=None, chunk=0,
                      chunks_per_call=1):
                """Every slot's state one token on; a dead slot's row (a
                prefill ahead of this step may just have filled it)
                stays as it is: left so by a ``token_fn`` that takes
                ``live``, else masked here, a pass over every slab."""
                if _takes_live(token_fn):
                    out, new = token_fn(rows, rec, live=live)
                    return out, {n: new[n].astype(v.dtype)
                                 for n, v in rec.items()}
                out, new = token_fn(rows, rec)
                return out, {
                    n: jnp.where(live.reshape((-1,) + (1,) * (v.ndim - 1)),
                                 new[n].astype(v.dtype), v)
                    for n, v in rec.items()}

            # where this step's K/V land: the table's page for the
            # position; page 0, offset 0 (trash) for a dead slot and
            # for a cache-hit first step
            write = live & ((a["flags"] & _TRASH) == 0)
            write_page = jnp.where(write, jnp.take_along_axis(
                page_table, (positions // page_size)[:, None],
                axis=1)[:, 0], 0)
            write_off = jnp.where(write, positions % page_size, 0)
            if mixed is None:
                # a model with pools alone that counts: its counters
                # ride the tokens' read-back as a mixed model's do
                mix = _Counting(model, self._tallies, live) \
                    if self._tallies else None
                logits, pools = self._token_step_body(
                    model, weights, _split_state(state), tokens,
                    positions, page_table, write_page, write_off, mix=mix)
                new_state = _join_state(pools)
            else:
                mix = _Mixers(mixed, recur, live,
                              interpret=self.config.interpret)
                if self._index is not None:
                    mix.attend_indexed = self._indexed_attend(
                        page_table, positions + 1, write_page, write_off)
                if self._window is not None:
                    # the position's page of the slot's own ring
                    # (kv_cache.WindowSpec.ring_table), trash for a dead
                    # slot
                    slot = jnp.arange(live.shape[0], dtype=jnp.int32)
                    ring = self._ring
                    mix.attend_window = self._window_attend(
                        positions + 1, jnp.where(
                            write, 1 + slot * ring
                            + (positions // page_size) % ring, 0),
                        write_off)
                logits, cache = self._token_step_body(
                    model, weights, mixed.split(state), tokens,
                    positions, page_table, write_page, write_off, mix=mix)
                new_state = mixed.join(cache)
            keys = jax.vmap(jax.random.fold_in)(a["key"], a["counter"])
            nxt = sample_tokens(keys, logits, a["temperature"],
                                a["top_k"], a["top_p"])
            nxt = jnp.where(live, nxt, 0)
            if mix is None:
                return (nxt, logits), new_state
            # the model's counters ride the tokens' read-back: the
            # step's one sync reads them too
            nxt = _with_counts(nxt, mix, self._tallies)
            if mixed is None:
                return (nxt, logits), new_state
            return (nxt, logits, mix.recorded()), new_state

        return jax.jit(step, donate_argnums=(0,))

    def _build_prefill_fn(self, t_pad: int, model, qz: bool):
        import jax
        import jax.numpy as jnp

        from ..ops.pallas_decode_attention import (
            decode_attention_reference, grouped_causal_attention)
        from ..ops.sampling_ops import sample_tokens

        cc = self._cache.config
        n_bp = t_pad // cc.page_size
        cdt = cc.dtype

        mixed = self._mixed if model is self.model else None
        row = _prefill_row(t_pad, cc.pages_per_slot, slot=mixed is not None)
        counted = () if model is not self.model else \
            self._prefill_tallies
        fresh_only = bool(per_slot_kinds(model)) or cc.latent

        @jax.named_scope("prefill_full")
        def prefill(state, weights, packed):
            a = _unpack(packed, row)
            tokens, length, pages = a["tokens"], a["length"], a["pages"]
            positions = jnp.arange(t_pad, dtype=jnp.int32)
            row_lengths = positions + 1

            def recur(token_fn, rows, rec, chunk_fn=None, chunk=0,
                      chunks_per_call=1):
                """The prompt's ``length`` real tokens from the zero
                state, so padding rows never touch the state: ``chunk``
                at a time through ``chunk_fn`` where the model hands one
                (the last call is told how many of its rows are real),
                else one after another through ``token_fn``; what the
                last one leaves goes into the slot's rows of the slabs.
                A call that takes ``chunks_per_call`` of the model's own
                chunks counts as that many scan steps, less those of
                the last call that hold no real row."""
                state0 = {n: jnp.zeros((1,) + v.shape[1:], v.dtype)
                          for n, v in rec.items()}
                if chunk_fn is None:
                    chunk, fn = 1, lambda r, n_real, st: token_fn(r, st)
                else:
                    fn = chunk_fn
                t_run = -(-t_pad // chunk) * chunk
                rows = {n: jnp.pad(v, ((0, t_run - t_pad),)
                                   + ((0, 0),) * (v.ndim - 1))
                        for n, v in rows.items()}
                at = lambda i: {n: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                    v, i * chunk, chunk, axis=0) for n, v in rows.items()}
                out = jax.eval_shape(fn, at(0), length, state0)[0]

                def scan_step(i, carry):
                    st, outs = carry
                    o, new = fn(at(i), jnp.minimum(length - i * chunk,
                                                   chunk), st)
                    return ({n: new[n].astype(v.dtype)
                             for n, v in st.items()},
                            jax.lax.dynamic_update_slice_in_dim(
                                outs, o, i * chunk, axis=0))

                steps = -(-length // chunk)
                st, outs = jax.lax.fori_loop(
                    0, steps, scan_step,
                    (state0, jnp.zeros((t_run,) + out.shape[1:], out.dtype)))
                mix.tally(_SCAN_TALLIES[0], steps if chunks_per_call == 1
                          else -(-length // (chunk // chunks_per_call)))
                mix.tally(_SCAN_TALLIES[1], length)
                return outs[:t_pad], {
                    n: jax.lax.dynamic_update_slice_in_dim(
                        v, st[n], a["slot"], axis=0) for n, v in rec.items()}

            def attend(l, q, k, v, pools, keep=None):       # [T_pad, H, D]
                k_pages, v_pages, k_scales, v_scales = pools
                # ``keep``: the rows the cache holds where they are not
                # the K the prompt attends (a latent cache's one pool);
                # a joint cache's are the keys, then the values
                if keep is None and v_pages is None and cc.joint:
                    keep = jnp.concatenate([k, v], axis=-1)
                k_pages, k_scales = kv_cache.write_prompt_layer(
                    k_pages, k_scales, l, k if keep is None else keep,
                    pages[:n_bp])
                if v_pages is not None:
                    v_pages, v_scales = kv_cache.write_prompt_layer(
                        v_pages, v_scales, l, v, pages[:n_bp])
                # attention in decode's own formulation through the SAME
                # cache representation the pages store — each row's
                # numerics are the ones decode will reproduce from the
                # pages, which is the bitwise prefix-cache contract.  The
                # softmax spans the prompt's own bucket: nothing is cached
                # beyond it, and the masked positions a wider one would add
                # weigh exactly zero.  In quantized mode the representation
                # is the local quant-dequant round trip (identical bytes to
                # what write_prompt_layer just stored).
                if qz:
                    kq, ksc = kv_cache.quantize_kv(k)
                    vq, vsc = kv_cache.quantize_kv(v)
                    kl = kv_cache.dequantize_kv(kq, ksc, cdt)
                    vl = kv_cache.dequantize_kv(vq, vsc, cdt)
                else:
                    kl, vl = k.astype(cdt), v.astype(cdt)
                if fresh_only or q.shape[-2] != k.shape[-2]:
                    # grouped-query heads (a group may be ONE head), or
                    # a model whose requests are all admitted fresh: the
                    # prompt's own width, the pages' dtype, the flash
                    # kernel or blocks of query rows (``prefill_walk``);
                    # no cached prefix to stay bitwise with
                    return grouped_causal_attention(
                        q, kl, vl, length=length,
                        use_pallas=self.config.use_pallas,
                        interpret=self.config.interpret), (
                        k_pages, v_pages, k_scales, v_scales)
                ctx = decode_attention_reference(
                    q, jnp.broadcast_to(kl[None], (t_pad,) + kl.shape),
                    jnp.broadcast_to(vl[None], (t_pad,) + vl.shape),
                    row_lengths)
                return ctx, (k_pages, v_pages, k_scales, v_scales)

            def causal(q, k, v, length, select=None):
                """A prompt's own rows attended causally, in the form
                ``attend`` runs a grouped model's (under ``select``
                where an indexer chose a row's keys)."""
                return grouped_causal_attention(
                    q, k, v, length=length, select=select,
                    use_pallas=self.config.use_pallas,
                    interpret=self.config.interpret)

            def attend_indexed(l, q, k, v, pools, index):
                """A layer that selects what it attends: the prompt's K,
                V and index keys into the slot's pages, then the
                indexer's prompt form over the rows as the pools keep
                them."""
                *kv, i_pages = pools
                kv[0], _ = kv_cache.write_prompt_layer(
                    kv[0], None, l, k, pages[:n_bp])
                kv[1], _ = kv_cache.write_prompt_layer(
                    kv[1], None, l, v, pages[:n_bp])
                i_pages = kv_cache.scatter_prompt_layer(
                    i_pages, l, index.key, pages[:n_bp])
                ctx = index.prompt(q, k.astype(cdt), v.astype(cdt),
                                   index.key[..., 0, :].astype(cdt), length)
                return ctx, (*kv, i_pages)

            def attend_window(l, q, k, v, pools, sinks):
                """A window layer of the prompt: masked to the window,
                with the sink, at the prompt's bucket; of its K/V only
                the tail a later token can still attend goes into the
                slot's ring."""
                ring_row = jnp.asarray(self._window.ring_table(
                    cc.num_slots, cc.page_size))[a["slot"]]
                pools = tuple(kv_cache.write_window_prompt_layer(
                    pool, l, val, length, ring_row)
                    for pool, val in zip(pools, (k, v)))
                with jax.named_scope(WINDOW_SCOPE):
                    ctx = grouped_causal_attention(
                        q, k.astype(cdt), v.astype(cdt),
                        window=self._window.window, sinks=sinks,
                        length=length, use_pallas=self.config.use_pallas,
                        interpret=self.config.interpret)
                return ctx, pools

            if mixed is None:
                mix = _Counting.of(model, attend, counted,
                                   positions < length)
                logits, pools = model.forward(              # [T_pad, V]
                    weights, tokens, positions, _split_state(state), mix)
                new_state = _join_state(pools)
            else:
                mix = _Mixers(mixed, recur, positions < length, attend,
                              attend_window, own_tallies=counted,
                              interpret=self.config.interpret, prompt=True,
                              read_row=length - 1,
                              attend_indexed=attend_indexed, causal=causal)
                logits, cache = model.forward(
                    weights, tokens, positions, mixed.split(state), mix)
                new_state = mixed.join(cache)
            # every row's logits, or the read row's alone (``read_row``)
            last = logits[0] if logits.shape[0] != t_pad else \
                jax.lax.dynamic_index_in_dim(
                    logits, length - 1, 0, keepdims=False)
            key0 = jax.random.fold_in(a["key"], 0)
            tok = sample_tokens(key0[None], last[None],
                                a["temperature"][None], a["top_k"][None],
                                a["top_p"][None])[0]
            if counted:
                # what the program counted rides the token's read-back
                tok = jnp.stack([tok] + [
                    jnp.asarray(mix.counts[n], jnp.int32) for n in counted])
            if mixed is None:
                return (tok, last), new_state
            return (tok, last, mix.recorded()), new_state

        return jax.jit(prefill, donate_argnums=(0,))

    def _build_rows_fn(self, n_rows: int, model):
        """Multi-row step: R query rows per slot written at explicit
        (page, offset) coords, attending over the slot's page table
        with per-row causal lengths.  ONE executable family serves
        chunked/suffix prefill (S=1, R=chunk rows) AND speculative
        verification (S=slots, R=spec_k+1): both are 'rows of a
        sequence extended through the cache', which is what keeps
        their logits bitwise-equal to the decode step and the
        full-recompute oracle."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas_decode_attention import paged_chunk_attention
        from ..ops.sampling_ops import greedy_sample, sample_tokens

        @jax.named_scope("prefill_rows")
        def rows_fn(state, weights, tokens, start, last_row, page_table,
                    write_page, write_off, base_keys, counters, temp,
                    top_k, top_p):
            positions = start[:, None] \
                + jnp.arange(n_rows, dtype=jnp.int32)[None, :]  # [S, R]
            # clip keeps padded/dead rows inside the positional table;
            # live rows are in range by the reservation accounting
            logits, pools = model.forward(                  # [S, R, V]
                weights, tokens,
                jnp.clip(positions, 0, model.max_seq_len - 1),
                # a model's counters: counted and dropped here
                _split_state(state), _Counting.of(
                    model, self._paged_attend(
                        paged_chunk_attention, page_table, positions + 1,
                        write_page, write_off)))
            greedy = greedy_sample(logits)                  # [S, R]
            last = jnp.take_along_axis(
                logits, last_row[:, None, None], axis=1)[:, 0]  # [S, V]
            keys = jax.vmap(jax.random.fold_in)(base_keys, counters)
            tok = sample_tokens(keys, last, temp, top_k, top_p)
            return (tok, greedy, logits), _join_state(pools)

        return jax.jit(rows_fn, donate_argnums=(0,))

    def _build_propose_fn(self, k_steps: int):
        """Draft proposal burst: k_steps+1 sequential draft-model steps
        in ONE dispatch (the +1 keeps the draft's own cache synced
        through the bonus position when every proposal is accepted).
        Write coords come from the page table in-fn; dead slots and
        out-of-range positions aim at the trash page."""
        import jax
        import jax.numpy as jnp

        from ..ops.sampling_ops import greedy_sample

        model = self._draft_model
        cc = self._cache.config
        p = cc.page_size
        pps = cc.pages_per_slot

        def propose(state, weights, tok0, start, live, trash_first,
                    page_table):
            pools = _split_state(state)
            cur = tok0
            props = []
            for j in range(k_steps + 1):
                pos = start + j                              # [S]
                idx = jnp.clip(pos // p, 0, pps - 1)
                pid = jnp.take_along_axis(
                    page_table, idx[:, None], axis=1)[:, 0]
                pid = jnp.where(live & (pos < cc.max_seq_len), pid, 0)
                if j == 0:
                    pid = jnp.where(trash_first, 0, pid)
                off = pos % p
                logits, pools = self._token_step_body(
                    model, weights, pools, cur,
                    jnp.clip(pos, 0, model.max_seq_len - 1),
                    page_table, pid, off)
                cur = greedy_sample(logits)                  # [S]
                props.append(cur)
            return (jnp.stack(props, axis=1),), _join_state(pools)

        return jax.jit(propose, donate_argnums=(0,))

    def _build_cow_fn(self):
        """Copy page ``src`` onto page ``dst`` across EVERY pool (all
        layers; target K/V + draft K/V when present) — the device half
        of copy-on-write.  One page is sliced out and updated into the
        donated pool in place: nothing pool-sized moves."""
        import jax
        from jax import lax

        def cow(state, src, dst):
            return ((), tuple(
                lax.dynamic_update_slice_in_dim(
                    pool, lax.dynamic_slice_in_dim(pool, src, 1, axis=1),
                    dst, axis=1)
                for pool in state))

        return jax.jit(cow, donate_argnums=(0,))

    def _prefill_fn(self, t_pad: int, which: str = "target",
                    quantized: Optional[bool] = None):
        qz = self._cache.config.quantized if quantized is None \
            else bool(quantized)
        key = (t_pad, which, qz)
        fn = self._prefill_fns.get(key)
        if fn is None:
            model = self.model if which == "target" else self._draft_model
            fn = self._prefill_fns[key] = self._build_prefill_fn(
                t_pad, model, qz)
            stat_add("decode_prefill_compiles")
        return fn

    def _rows_fn(self, n_rows: int, n_slots: int, which: str = "target"):
        key = (n_rows, n_slots, which)
        fn = self._rows_fns.get(key)
        if fn is None:
            model = self.model if which == "target" else self._draft_model
            fn = self._rows_fns[key] = self._build_rows_fn(n_rows, model)
            stat_add("decode_prefill_compiles")
        return fn

    # -- client side ------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens=None,
               deadline_ms=_UNSET, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0,
               seed: Optional[int] = None,
               on_token: Optional[Callable[[int], None]] = None,
               record_logits: bool = False,
               speculative: Optional[bool] = None,
               extract_kv: bool = False,
               kv_import=None) -> DecodeRequest:
        from ..observe.request_trace import get_trace_store

        c = self.config
        prompt = [int(t) for t in prompt]
        trace = get_trace_store().start(
            "decode", replica=self.name, prompt_len=len(prompt),
            max_new_tokens=None if max_new_tokens is None
            else int(max_new_tokens))
        try:
            return self._submit_traced(
                trace, prompt, max_new_tokens, deadline_ms, temperature,
                top_k, top_p, seed, on_token, record_logits, speculative,
                extract_kv, kv_import)
        except Exception as e:
            # submit-time rejection IS a terminal outcome: count it,
            # record its (instant) terminal latency so error-rate
            # denominators include rejects, and tail-retain the (tiny)
            # trace so /debug/request/<id> can answer "why did my
            # request never run".  Only SERVER-fault rejections burn
            # the SLO budget (overload shedding, draining) — a buggy
            # client hammering an invalid prompt must not page anyone.
            outcome = "cancelled" if isinstance(e, ServerClosedError) \
                else "rejected"
            stat_add(f"decode_requests_total_{outcome}")
            latency = time.monotonic() - trace.t_start
            stat_time("decode_request_latency_seconds", latency)
            summary = {"outcome": outcome,
                       "latency_s": round(latency, 6),
                       "ttft_s": None, "tpot_s": None, "n_tokens": 0,
                       "prompt_len": len(prompt)}
            violations = ()
            if isinstance(e, (QueueFullError, ServerClosedError)):
                try:
                    from ..observe import slo as _slo

                    violations = _slo.observe_request(summary)
                except Exception:  # noqa: BLE001 — never mask the
                    stat_add("request_trace_errors")  # rejection
            summary.pop("outcome")  # stored top-level on the trace
            get_trace_store().finish(
                trace, outcome=outcome,
                reason=f"{type(e).__name__}: {e}",
                violations=violations, **summary)
            raise

    def _submit_traced(self, trace, prompt, max_new_tokens, deadline_ms,
                       temperature, top_k, top_p, seed, on_token,
                       record_logits, speculative, extract_kv=False,
                       kv_import=None) -> DecodeRequest:
        c = self.config
        if not prompt:
            raise ValueError("prompt must hold at least one token id")
        if (extract_kv or kv_import is not None) \
                and per_slot_kinds(self.model):
            kind, keeps = per_slot_kinds(self.model)[0]
            raise ValueError(
                "disaggregated serving hands a prompt over as its K/V "
                f"pages (extract_kv / kv_import); this model's {kind} "
                f"layers {keeps}, which no exported page holds, so the "
                "hand-over would decode from the wrong state")
        if (extract_kv or kv_import is not None) \
                and self._cache.config.latent:
            raise ValueError(
                "disaggregated serving hands a prompt over as its K/V "
                "pages (extract_kv / kv_import); this model keeps a latent "
                "page (one row a position for keys and values), which the "
                "export and the install are not built for")
        if (extract_kv or kv_import is not None) and self._index is not None:
            raise ValueError(
                "disaggregated serving hands a prompt over as its K/V "
                "pages (extract_kv / kv_import); this model keeps an "
                "index pool (a third array a position), which the export "
                "and the install are not built to carry")
        if kv_import is not None:
            # migrated admission (serving/disagg.py): validate the
            # payload against THIS engine's pool geometry at submit
            # time — a mismatch must reject loudly, never corrupt pools
            cc = self._cache.config
            if extract_kv:
                raise ValueError(
                    "kv_import and extract_kv are mutually exclusive "
                    "(a request is either the prefill leg or the "
                    "decode leg of a disagg handoff, not both)")
            if speculative:
                raise ValueError(
                    "kv_import cannot be speculative: the migration "
                    "payload carries the target pools only — the "
                    "draft pools never saw the prompt K/V")
            if bool(kv_import.quantized) != bool(cc.quantized):
                raise ValueError(
                    f"kv_import quantized={kv_import.quantized} but "
                    f"this engine's cache quantized={cc.quantized} — "
                    f"prefill and decode replicas must agree on "
                    f"FLAGS_decode_kv_quant")
            if int(kv_import.page_size) != cc.page_size:
                raise ValueError(
                    f"kv_import page_size {kv_import.page_size} != "
                    f"engine page_size {cc.page_size}")
            if int(kv_import.n_tokens) != len(prompt):
                raise ValueError(
                    f"kv_import covers {kv_import.n_tokens} tokens but "
                    f"the prompt has {len(prompt)}")
            if int(kv_import.n_pages) != cc.pages_for(len(prompt)):
                raise ValueError(
                    f"kv_import carries {kv_import.n_pages} pages but "
                    f"the prompt needs {cc.pages_for(len(prompt))}")
        if speculative:
            # loud submit-time rejection: a request that ASKS for
            # speculative decoding must get it or fail, never silently
            # degrade
            if self._draft_model is None:
                raise ValueError(
                    "speculative=True but the engine has no draft "
                    "model (DecodeEngine(draft_model=, draft_weights=))")
            if c.spec_k <= 0:
                raise ValueError(
                    "speculative=True but FLAGS_decode_spec_k / "
                    "DecodeConfig.spec_k is 0")
            if float(temperature) > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only (bitwise "
                    "acceptance); submit with temperature=0")
        if max_new_tokens is None:
            max_new_tokens = c.max_new_tokens
        if len(prompt) + int(max_new_tokens) > c.max_seq_len:
            raise RequestTooLargeError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the slot capacity "
                f"({c.max_seq_len}); raise FLAGS_decode_max_seq_len or "
                f"shorten the request")
        cc = self._cache.config
        need = cc.pages_for(len(prompt) + int(max_new_tokens))
        if need > cc.num_pages - 1:  # page 0 is trash, never allocatable
            # an unsatisfiable reservation must be rejected HERE: queued
            # it would head-of-line-block the engine forever (no finish
            # can ever free enough pages)
            raise RequestTooLargeError(
                f"request needs {need} cache pages but the pool only "
                f"has {cc.num_pages - 1}; raise num_pages or shorten "
                f"the request")
        self._buckets.seq_bucket(len(prompt))  # raises RequestTooLarge
        if deadline_ms is _UNSET:
            deadline_ms = c.default_deadline_ms
        deadline = None if deadline_ms is None \
            else time.monotonic() + float(deadline_ms) / 1e3
        with self._cond:
            if self._closing:
                raise ServerClosedError("decode engine is stopping")
            if len(self._queue) >= c.max_queue:
                stat_add("decode_rejected_queue_full")
                raise QueueFullError(
                    f"decode queue is at capacity ({c.max_queue})")
            if seed is None:
                seed = self._seq
            self._seq += 1
            req = DecodeRequest(prompt, max_new_tokens, deadline,
                                temperature, top_k, top_p, seed,
                                on_token, record_logits=record_logits,
                                speculative=speculative,
                                extract_kv=extract_kv,
                                kv_import=kv_import)
            req.trace = trace
            self._queue.append(req)
            # resolved defaults ride the event, not trace.attrs: the
            # trace is already visible to concurrent /debug readers
            # and attrs must stay structurally frozen after start()
            trace.event("enqueue", queue_depth=len(self._queue),
                        max_new_tokens=int(max_new_tokens),
                        seed=int(seed),
                        deadline_ms=None if deadline_ms is None
                        else float(deadline_ms))
            stat_add("decode_requests")
            stat_set("decode_queue_depth", len(self._queue))
            self._cond.notify_all()
        return req

    def generate(self, prompt, **kw) -> List[int]:
        return self.submit(prompt, **kw).result()

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "DecodeEngine":
        with self._cond:
            if self._thread is not None:
                return self
            self._closing = self._abort = False
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"decode-{self.name}")
            self._thread.start()
        from ..observe import flight as _flight

        _flight.record("serving/decode_start", name=self.name,
                       slots=self.config.slots,
                       max_seq_len=self.config.max_seq_len,
                       page_size=self.config.page_size,
                       prefix_cache=self.config.prefix_cache,
                       kv_quant=self.config.kv_quant,
                       spec_k=self.config.spec_k
                       if self.spec_enabled else 0)
        stat_set("decode_kv_quant_enabled",
                 1 if self.config.kv_quant else 0)
        stat_set("decode_kv_page_bytes", self._cache.config.page_bytes())
        stat_set("decode_state_bytes", self._cache.state_bytes())
        stat_set("decode_window_bytes", self._cache.window_bytes())
        stat_set("decode_latent_bytes", self._cache.latent_bytes())
        stat_set("decode_index_bytes", self._cache.index_bytes())
        stat_set("decode_cache_layers", self._cache.config.num_layers)
        stat_set("decode_kv_pool_bytes", self._cache.config.cache_bytes())
        from ..ops.pallas_decode_attention import feed_bits

        stat_set("decode_attn_feed_bits",
                 feed_bits(self._cache.config.store_dtype))
        return self

    def stop(self, drain: bool = True):
        with self._cond:
            self._closing = True
            if not drain:
                self._abort = True
                while self._queue:
                    req = self._queue.popleft()
                    if req._finish(error=ServerClosedError(
                            "engine stopped before the request ran")):
                        stat_add("decode_cancelled")
                stat_set("decode_queue_depth", 0)
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        from ..observe import flight as _flight

        _flight.record("serving/decode_stop", name=self.name,
                       drain=bool(drain))

    def __enter__(self) -> "DecodeEngine":
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=exc[0] is None)
        return False

    # -- scheduler --------------------------------------------------------
    @property
    def live_slots(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    @property
    def free_slots(self) -> int:
        return self.config.slots - self.live_slots

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    def _expire(self, req, where: str) -> None:
        if req._finish(error=DeadlineExceededError(
                f"deadline exceeded {where}")):
            stat_add("decode_deadline_exceeded")

    def _reap_queue_locked(self):
        now = time.monotonic()
        live = []
        for r in self._queue:
            if r.done():
                continue
            if r.expired(now):
                self._expire(r, "while queued")
                continue
            live.append(r)
        if len(live) != len(self._queue):
            self._queue = collections.deque(live)
            stat_set("decode_queue_depth", len(self._queue))

    def _admit_locked(self):
        admitted = []
        while self._queue:
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                break
            req = self._queue[0]
            if req.done():
                self._queue.popleft()
                continue
            if req.expired():
                self._queue.popleft()
                self._expire(req, "while queued")
                continue
            # shared-aware worst-case reservation: pages for prompt +
            # max_new minus every prefix-cache hit, with a CoW spare
            # held back for a borrowed partial page — a decode step can
            # still never die on cache exhaustion mid-flight
            slot = free[0]
            need = len(req.prompt) + req.max_new_tokens
            self._admitting = req
            try:
                # a migrated admission claims ALL-FRESH pages (no
                # prefix lookup): the installed pages must be solely
                # owned — cross-engine sharing of migrated bytes is
                # exactly what the disagg refcount contract forbids
                info = self._cache.claim(
                    slot, need,
                    prompt=None if req.kv_import is not None
                    else req.prompt)
            finally:
                self._admitting = None
            if info is None:
                stat_add("decode_admission_blocked_pages")
                self._tev(req, "admission_blocked",
                          reason="pages",
                          free_pages=self._cache.allocator.num_free)
                break  # FIFO head-of-line: wait for pages to free
            self._queue.popleft()
            st = _SlotState(req, _key_words(req.seed))
            st.spec = (self.spec_enabled and req.temperature <= 0.0
                       and req.speculative is not False
                       and req.kv_import is None)
            if req.kv_import is not None:
                self._account_migrated(slot, st, req)
            else:
                self._account_claim(slot, st, info)
            self._slots[slot] = st
            admitted.append((slot, req))
        stat_set("decode_queue_depth", len(self._queue))
        return admitted

    def _account_migrated(self, slot: int, st: _SlotState, req) -> None:
        """Admit a request whose prompt K/V arrives as a migration
        payload (disaggregated serving): install the pages into the
        slot's fresh claim, then start the slot exactly like a
        full-prefix-cache hit — the pages hold prompt positions
        ``0..n-1``, so the first decode step re-derives the last prompt
        position's logits (its own K/V write aims at trash) and samples
        the first token with ``fold_in(base_key, 0)``.  That is the
        SAME sampling path as a local prefill's first token, which is
        what makes migrated decode bitwise-equal to local."""
        n = len(req.prompt)
        self._cache.install_pages(slot, req.kv_import)
        st.phase = "decode"
        st.write_trash_once = True
        st.last_token = req.prompt[-1]
        st.prefill_pos = n
        self._cache.lengths[slot] = n - 1
        stat_add("decode_migrated_admissions")
        self._tev(req, "admit", slot=slot,
                  queue_wait_ms=round(
                      (st.t_admit - req.t_enqueue) * 1e3, 3),
                  migrated_pages=req.kv_import.n_pages,
                  migrated_bytes=req.kv_import.nbytes,
                  prefill_skipped=True)
        # drop the payload reference: the arrays live in the pools now,
        # and holding them would pin the transport buffers for the
        # request's whole lifetime
        req.kv_import = None

    def _account_claim(self, slot: int, st: _SlotState, info) -> None:
        """Fold one admission's prefix-cache outcome into the slot's
        phase plan and the hit-rate accounting."""
        req = st.req
        n = len(req.prompt)
        if self._cache.prefix_bypassed:
            # asked for, and left out: a model with recurrent layers
            # admits every request as fresh (serving/kv_cache.py)
            stat_add("decode_prefix_bypassed")
        self._hit_pages += info.hit_pages
        self._prompt_pages += info.prompt_pages
        if info.hit_pages:
            stat_add("decode_prefix_pages_hit", info.hit_pages)
        stat_add("decode_prefix_pages_total", info.prompt_pages)
        total = stat_get("decode_prefix_pages_total")
        if total:
            hits = stat_get("decode_prefix_pages_hit")
            # deprecated integer-percent form (kept for dashboards) +
            # the float-precision _ppm companion (same pattern as
            # cluster_step_time_skew_ppm)
            stat_set("decode_cache_hit_rate", int(100 * hits / total))
            stat_set("decode_cache_hit_rate_ppm",
                     int(1e6 * hits / total))
        stat_set("decode_shared_pages", self._cache.shared_pages)
        self._tev(req, "admit", slot=slot,
                  queue_wait_ms=round(
                      (st.t_admit - req.t_enqueue) * 1e3, 3),
                  prompt_pages=info.prompt_pages,
                  fresh_pages=info.fresh_pages,
                  hit_pages=info.hit_pages,
                  hit_tokens=info.hit_tokens,
                  cow_spare=bool(info.partial),
                  prefill_skipped=info.hit_tokens >= n)
        if info.hit_tokens >= n:
            # the ENTIRE prompt is cache-covered: skip prefill — the
            # first decode step re-derives the last prompt position's
            # logits (its K/V write aims at trash: the shared pages
            # already hold that position) and samples the first token
            st.phase = "decode"
            st.write_trash_once = True
            st.last_token = req.prompt[-1]
            st.prefill_pos = n
            # the first step's query is the LAST prompt position: its
            # K/V (and everything before) is already in the shared
            # pages, so the length cursor starts one short of the
            # prompt and the step's own write goes to trash
            self._cache.lengths[slot] = n - 1
            stat_add("decode_prefill_skipped")
        else:
            st.phase = "prefill"
            st.prefill_pos = info.hit_tokens  # page-aligned by design

    def _release(self, slot: int):
        st = self._slots[slot]
        register = None
        if st is not None and self._cache.prefix is not None \
                and st.phase == "decode" \
                and (not self.spec_enabled or st.spec):
            # register this slot's pages for future prefix hits — only
            # when the draft pools are synced too (a non-speculative
            # slot on a spec engine never wrote draft K/V; stale draft
            # bytes could not corrupt output, only acceptance, but we
            # keep the index clean).  Content = prompt + generated,
            # truncated to the positions actually written — minus any
            # trailing positions a spec slot wrote through the normal
            # step (target-only; the draft bytes there are stale).
            seq = st.req.prompt + st.req.generated
            register = seq[:int(self._cache.lengths[slot])
                           - st.draft_lag]
        # release BEFORE clearing the slot so the cache's register/
        # evict events can still be attributed to the owning request
        self._cache.release(slot, register_tokens=register)
        self._slots[slot] = None
        stat_set("decode_free_pages", self._cache.allocator.num_free)
        stat_set("decode_shared_pages", self._cache.shared_pages)

    def _export_slot_kv(self, slot: int) -> None:
        """Gather the slot's prompt-covering pages into a migration
        payload on ``req.kv_export`` — the disagg prefill->decode
        handoff.  Runs on the engine thread right before the slot
        releases, so the pages still hold positions ``0..n-1`` and the
        gather cannot race a donated step."""
        st = self._slots[slot]
        req = st.req
        cc = self._cache.config
        n = len(req.prompt)
        if int(self._cache.lengths[slot]) < n - 1:
            return  # prefill never covered the prompt; router re-runs
        n_pages = cc.pages_for(n)
        pages = self._cache.slot_pages(slot)[:n_pages]
        with otrace.span("serving/migrate_export", slot=slot,
                         pages=n_pages):
            arrays = self._cache.export_pages(pages)
        req.kv_export = kv_cache.KVPageExport(
            n_tokens=n, n_pages=n_pages, src_pages=pages,
            arrays=arrays, quantized=cc.quantized,
            page_size=cc.page_size)
        stat_add("decode_kv_exports")
        self._tev(req, "kv_export", pages=n_pages,
                  bytes=req.kv_export.nbytes)

    def _finish_slot(self, slot: int, error=None):
        st = self._slots[slot]
        if error is None and self._abort:
            # stop(drain=False) landed while this slot's dispatch was in
            # flight: an aborted replica completes nothing, exactly as
            # _loop fails every live slot it still finds (the disagg
            # router re-dispatches the leg to a survivor)
            error = ServerClosedError("engine stopped mid-generation")
        if error is None and st.req.extract_kv \
                and st.phase == "decode":
            # export BEFORE _finish: the handoff thread wakes on the
            # request's completion and must find the payload attached
            try:
                self._export_slot_kv(slot)
            except Exception as e:  # noqa: BLE001 — a failed export
                # must fail the REQUEST (the router re-dispatches), not
                # the engine loop
                error = e
        if error is None:
            if st.req._finish():
                stat_add("decode_completed")
        else:
            if st.req._finish(error=error):
                stat_add("decode_failed")
        self._release(slot)

    def _reap_live(self):
        """The mid-decode deadline reap: runs at EVERY step boundary so
        a stalled/abandoned client frees its slot now, not after
        max_new_tokens."""
        now = time.monotonic()
        for i, st in enumerate(self._slots):
            if st is None:
                continue
            if st.req.done():  # client-side reap/abandon won the race
                stat_add("decode_abandoned")
                self._release(i)
            elif st.req.expired(now):
                self._expire(st.req, "mid-decode (slot freed)")
                self._release(i)

    def _loop(self):
        """One iteration: the arguments of the joint step of the slots
        that decode now are built and uploaded; the queue is admitted,
        as late as anything can still go ahead of that step; the
        whole-prompt prefills of the admitted are handed to the device
        and the step right behind them, neither waited for; THEN the
        joint step handed over an iteration ago is read and delivered,
        then the prefills' first tokens.  The step just handed over
        stays in flight (``_flying``) into the next iteration: the host
        delivers, reaps, builds and hands over under it, and the device
        finds the next step queued when this one ends.

        What makes the step ahead possible: a slot that was live in the
        step in flight takes its token from that step's output on the
        device (``_CARRY``), and what the host knows without the token
        it computes ahead (``_SlotState.ahead``): the position, the
        sampler's counter, the write's page, and which slots END at the
        step in flight by their budget (those are left out, so a budget
        finish costs no row and frees its slot when it always did).
        What it cannot know the delivery settles: a row is delivered
        only while its slot still holds the request it was computed for
        (an end token, a reap, an abort or a failed read-back may have
        released it, and an admission refilled it, meanwhile; counter
        ``decode_rows_discarded``).  Every release while a step is in
        flight is safe because the device runs programs in hand-over
        order: whatever is handed over for a freed page or slot (a
        prefill, a copy, an import) runs after the step that may still
        write it, that write lies one position past anything the prefix
        index registered, and the export at a finish reads prompt pages
        no step writes.

        The depth is 0 or 1, chosen from what the loop observes, by no
        knob.  A step at which a slot ENDS by its budget (known at its
        hand-over) is read before anything is handed over behind it: a
        freed slot is where the next admission comes from (a queued
        request, or in a closed loop the caller's next), and its prefill
        then finds the device free instead of behind a step's remainder
        (measured: `PERF.md` section 6, PR 38).  While a slot
        speculates the loop runs one step at a time, each joint step
        read in its own iteration (the order the loop had): a
        speculative round runs to its own syncs.  Chunked and
        suffix prefills queue behind the step in flight and run to
        their own sync; before an idle wait, a stop and an abort nothing
        is left in flight.

        A request admitted here joins the NEXT step (its first token is
        not on the host when this one is handed over).  Nothing waits
        for a caller: one that submits while the arguments are built (a
        reply's caller gets the interpreter in their upload, the first
        place after ``step_deliver`` where this thread lets go of it)
        makes this admission, a later one the next."""
        while True:
            self._iter += 1
            with otrace.span("serving/reap", iter=self._iter):
                self._reap_live()
            if self._flying is not None and (
                    not self.live_slots or any(
                        st is not None and st.ahead and st.n_generated
                        + st.ahead >= st.req.max_new_tokens
                        for st in self._slots)):
                # a slot ends at the step in flight by its budget, and a
                # freed slot is where the next admission comes from: that
                # step is read BEFORE anything is handed over behind it,
                # so that the admitted prefill finds the device free.
                # (Or every row's slot was released: read and dropped.)
                self._drain()
            step, ahead_ok = self._prepare_decode_round()
            # ``with self._cond:`` with the wait for the lock (callers
            # hold it while they submit) as a phase of its own, so that
            # the iteration is spanned end to end
            with otrace.span("serving/lock_wait", iter=self._iter):
                self._cond.acquire()
            try:
                if self._abort:
                    for i, st in enumerate(self._slots):
                        if st is not None:
                            self._finish_slot(i, ServerClosedError(
                                "engine stopped mid-generation"))
                    self._drain()
                    return
                with otrace.span("serving/admit", iter=self._iter):
                    self._reap_queue_locked()
                    admitted = self._admit_locked()
                    otrace.set_span_args(admitted=len(admitted),
                                         queued=len(self._queue))
                if not admitted and not self.live_slots:
                    if self._closing and not self._queue:
                        return
                    # short cap keeps queued deadlines (and a pages-
                    # blocked head) honest while idle
                    # waiting for work is not the loop's turnaround
                    self._t_tokens = None
                    with otrace.span("serving/idle_wait",
                                     iter=self._iter):
                        self._cond.wait(0.05 if self._queue else None)
                    continue
            finally:
                self._cond.release()
            finishes = self._start_prefills()
            handed = None
            if step is not None:
                handed = self._dispatch_step(*step, ahead=len(finishes))
            self._drain()   # the step handed over an iteration ago
            for finish in finishes:
                finish()
            if ahead_ok:
                self._flying = handed
            elif handed is not None:
                handed()
            if handed is None:
                # no joint step in this iteration: the next one's
                # hand-over follows more than the loop's own path
                self._t_tokens = None

    def _drain(self) -> None:
        """Read and deliver the joint step in flight, if there is one."""
        flying, self._flying = self._flying, None
        if flying is not None:
            flying()

    # -- device work: prefill ---------------------------------------------
    def _start_prefills(self):
        """Advance prefill-phase slots.  Chunked mode dispatches ONE
        chunk per engine-loop iteration (round-robin across prefilling
        slots) so the decoding slots keep stepping between chunks;
        unchunked mode completes each prefill in one dispatch.  A
        whole-prompt prefill is only handed to the device here:
        returned are the halves that wait for its token and deliver
        it.  The other paths run to their end."""
        pre = [i for i, st in enumerate(self._slots)
               if st is not None and st.phase == "prefill"]
        finishes = []
        if not pre:
            return finishes
        chunk = self.config.prefill_chunk_pages
        if chunk > 0:
            pick = min(pre, key=lambda i:
                       (i - self._prefill_rr) % self.config.slots)
            self._prefill_rr = (pick + 1) % self.config.slots
            self._run_prefill_rows(
                pick, chunk * self.config.page_size)
        else:
            for i in pre:
                st = self._slots[i]
                if st.prefill_pos == 0:
                    finishes.append(self._start_prefill_full(i))
                else:
                    # prefix-cache suffix: only the unmatched tail of
                    # the prompt is computed, in one dispatch
                    rows = self._buckets.seq_bucket(
                        len(st.req.prompt) - st.prefill_pos)
                    self._run_prefill_rows(i, rows)
        return finishes

    def _prefill_args(self, t_pad: int, prompt, pages=0, key=0,
                      temperature=0.0, top_k=0, top_p=1.0,
                      slot=0) -> np.ndarray:
        """Everything one whole-prompt prefill takes after its weights,
        as the int32 words of one ``_prefill_row`` record (host).  The
        defaults are a greedy request that writes the trash page (and,
        where the model keeps recurrent state, slot 0's rows)."""
        rec = np.zeros(1, _prefill_row(
            t_pad, self._cache.config.pages_per_slot,
            slot=self._mixed is not None))
        if self._mixed is not None:
            rec["slot"] = slot
        rec["tokens"][0, :len(prompt)] = prompt
        rec["length"] = len(prompt)
        rec["pages"] = pages
        rec["key"] = key
        rec["temperature"] = temperature
        rec["top_k"] = top_k
        rec["top_p"] = top_p
        return _words(rec)[0]

    def _start_prefill_full(self, slot: int):
        """The whole-prompt prefill fast path (no cache hit, chunking
        off): page-wholesale K/V writes + locally-built attention at
        the bucket's width, one dispatch.  Returns the half that waits
        for the first token and delivers it."""
        st = self._slots[slot]
        req = st.req

        def failed(e):  # fault isolation per request
            stat_add("decode_prefill_errors")
            self._finish_slot(slot, e)

        try:
            t_pad = self._buckets.seq_bucket(len(req.prompt))
            attrs = {"iter": self._iter, "slot": slot, "bucket": t_pad,
                     "req": _rid(req)}
            t0 = time.monotonic()
            with otrace.span("serving/prefill_args", **attrs):
                up = _Uploads()
                packed = up(self._prefill_args(
                    t_pad, req.prompt, self._cache.page_table[slot],
                    st.base_key, req.temperature, req.top_k, req.top_p,
                    slot=slot))
                up.record()
            with otrace.span("serving/prefill_dispatch", **attrs):
                tok, last, *recorded = self._exe.run_persistent(
                    self._prefill_fn(t_pad), self._state_vars,
                    args=(self.weights, packed), scope=self._scope)
                recorded = recorded[0] if recorded else {}
                if st.spec:
                    # mirror the prefill into the draft's pools (same
                    # page ids, the same uploaded arguments) so
                    # proposals can read the prompt
                    self._exe.run_persistent(
                        self._prefill_fn(t_pad, "draft"),
                        self._draft_state_vars,
                        args=(self.draft_weights, packed),
                        scope=self._scope)
        except Exception as e:  # noqa: BLE001
            failed(e)
            return lambda: None

        def finish():
            try:
                with otrace.span("serving/prefill_sync", **attrs):
                    # the prefill's sync point: the token, then what
                    # the program counted
                    first, *counted = (int(x) for x in np.asarray(
                        tok).reshape(-1))
                # through the sync: the time until the token is on the
                # host
                dur = time.monotonic() - t0
                stat_time("decode_prefill_seconds", dur)
                with _Delivery(self, "serving/prefill_deliver", attrs):
                    self._tev(req, "prefill", slot=slot, bucket=t_pad,
                              tokens=len(req.prompt),
                              dur_ms=round(dur * 1e3, 3))
                    stat_add("decode_prefills")
                    for name, x in zip(self._prefill_tallies, counted):
                        stat_add(name, x)
                    # how much of the prompt's attention is work: the
                    # positions every row's softmax spans (the bucket)
                    # against those a row can see (the causal triangle)
                    n = len(req.prompt)
                    attended, live, forms = self._prefill_keys(t_pad, n)
                    stat_add("decode_prefill_keys_attended", attended)
                    stat_add("decode_prefill_keys_live", live)
                    # layer-calls by the form their attention ran in
                    for form, layers in forms.items():
                        stat_add("decode_prefill_attn_" + form, layers)
                    record_pad_waste(n, t_pad)
                    st.prefill_pos = n
                    st.phase = "decode"
                    self._cache.lengths[slot] = n
                    if req.record_logits:
                        req.logits_trace.append(np.asarray(last))
                        for name, rows in recorded.items():
                            req.records.setdefault(name, []).append(
                                np.asarray(rows)[:len(req.prompt)])
                    self._deliver(slot, first)
            except Exception as e:  # noqa: BLE001
                failed(e)

        return finish

    def _run_prefill_rows(self, slot: int, rows: int):
        """One prefill chunk of ``rows`` positions starting at the
        slot's prefill cursor (page-aligned).  Serves both chunked
        prefill and the prefix-cache suffix (start > 0): attention
        gathers the already-present pages for positions below the
        cursor, so the chunk's logits stay bitwise-equal to a full
        prefill.  The FINAL chunk samples the request's first token."""
        st = self._slots[slot]
        req = st.req
        cc = self._cache.config
        try:
            n = len(req.prompt)
            start = st.prefill_pos
            n_live = min(rows, n - start)
            final = start + n_live >= n
            attrs = {"iter": self._iter, "slot": slot, "bucket": rows,
                     "start": start, "req": _rid(req)}
            t0 = time.monotonic()
            with otrace.span("serving/prefill_args", **attrs):
                up = _Uploads()
                tokens = np.zeros((1, rows), np.int32)
                tokens[0, :n_live] = req.prompt[start:start + n_live]
                write_page = np.zeros((1, rows), np.int32)
                write_off = np.zeros((1, rows), np.int32)
                for r in range(n_live):
                    pos = start + r
                    write_page[0, r] = self._cache.page_table[slot][
                        pos // cc.page_size]
                    write_off[0, r] = pos % cc.page_size
                args = lambda w: (  # noqa: E731
                    w, up(tokens),
                    up.host(np.asarray([start], np.int32)),
                    up.host(np.asarray([min(n - 1 - start, rows - 1)],
                                       np.int32)),
                    up(self._cache.page_table[slot:slot + 1]),
                    up(write_page), up(write_off),
                    up(st.base_key[None]),
                    up.host(np.zeros((1,), np.int32)),
                    up.host(np.asarray([req.temperature], np.float32)),
                    up.host(np.asarray([req.top_k], np.int32)),
                    up.host(np.asarray([req.top_p], np.float32)))
                target_args = args(self.weights)
                draft_args = args(self.draft_weights) if st.spec else None
                up.record()
            with otrace.span("serving/prefill_dispatch", **attrs):
                tok, _greedy, logits = self._exe.run_persistent(
                    self._rows_fn(rows, 1), self._state_vars,
                    args=target_args, scope=self._scope)
                if st.spec:
                    self._exe.run_persistent(
                        self._rows_fn(rows, 1, "draft"),
                        self._draft_state_vars,
                        args=draft_args, scope=self._scope)
            if final:
                # only the chunk that samples is read back: an earlier
                # chunk's observation ends with its dispatch
                with otrace.span("serving/prefill_sync", **attrs):
                    tok = int(np.asarray(tok)[0])
            dur = time.monotonic() - t0
            stat_time("decode_prefill_seconds", dur)
            with _Delivery(self, "serving/prefill_deliver", attrs):
                stat_add("prefill_chunks")
                record_pad_waste(n_live, rows)
                self._prefill_chunk_count += 1
                st.chunks += 1
                self._tev(req, "prefill_chunk", slot=slot, start=start,
                          rows=rows, live=n_live, final=final,
                          dur_ms=round(dur * 1e3, 3))
                st.prefill_pos += n_live
                if final:
                    stat_add("decode_prefills")
                    st.phase = "decode"
                    self._cache.lengths[slot] = n
                    if req.record_logits:
                        req.logits_trace.append(
                            np.asarray(logits)[0, n - 1 - start].copy())
                    self._deliver(slot, tok)
        except Exception as e:  # noqa: BLE001 — fault isolation per req
            stat_add("decode_prefill_errors")
            self._finish_slot(slot, e)

    # -- device work: decode ----------------------------------------------
    def _deliver(self, slot: int, token: int):
        """Account one sampled token for a live slot; finish + free the
        slot the moment its request is done."""
        st = self._slots[slot]
        now = time.monotonic()
        if st.n_generated > 0:
            stat_time("tpot_seconds", now - st.t_last)
        st.t_last = now
        st.n_generated += 1
        st.last_token = token
        self.tokens_total += 1
        stat_add("decode_tokens_total")
        carried = self._carried  # None: no sink takes the open span
        if carried is None:
            st.req._emit(token)
        else:
            t0 = time.perf_counter_ns()
            st.req._emit(token)
            carried[0] += 1
            carried[2] += time.perf_counter_ns() - t0
        self._tev(st.req, "token", slot=slot, token=int(token),
                  n=st.n_generated)
        eos = self.config.eos_id
        if eos is not None and token == eos:
            st.req.finish_reason = "eos"
        elif st.n_generated >= st.req.max_new_tokens:
            st.req.finish_reason = "budget"
        else:
            return
        if carried is None:
            self._finish_slot(slot)
        else:
            t0 = time.perf_counter_ns()
            self._finish_slot(slot)
            carried[1] += 1
            carried[3] += time.perf_counter_ns() - t0

    def _perform_cow(self, slot, plans):
        """Run the device half of every planned copy-on-write BEFORE
        the write dispatch that needed it (the host tables were already
        swapped by plan_cow)."""
        if not plans:
            return
        if self._cow_fn is None:
            self._cow_fn = self._build_cow_fn()
        st = self._slots[slot]
        for src, dst in plans:
            t0 = time.monotonic()
            self._exe.run_persistent(
                self._cow_fn, self._cow_state,
                args=(np.int32(src), np.int32(dst)), scope=self._scope)
            stat_add("decode_cow_copies")
            self._cow_copies += 1
            if st is not None:
                self._tev(st.req, "cow", slot=slot, src=int(src),
                          dst=int(dst),
                          dur_ms=round((time.monotonic() - t0) * 1e3, 3))

    def _prepare_decode_round(self):
        """The round of the slots that decode now: speculative rounds
        run to their end; for the joint step of the rest, what
        ``_dispatch_step`` takes (None without one), and whether that
        step may stay in flight into the next iteration.  A slot whose
        budget ends at the step in flight is in no round: its last
        token is on its way."""
        speculates = any(st is not None and st.spec for st in self._slots)
        if speculates:
            # a speculative round runs to its own syncs on what the
            # host holds: nothing stays in flight beside it
            self._drain()
        decoding = [i for i, st in enumerate(self._slots)
                    if st is not None and st.phase == "decode"
                    and st.n_generated + st.ahead < st.req.max_new_tokens]
        if not decoding:
            return None, not speculates
        stat_max("decode_slot_occupancy_max", len(decoding))
        spec = [i for i in decoding
                if self._slots[i].spec
                and (self._slots[i].req.max_new_tokens
                     - self._slots[i].n_generated) >= 2]
        if spec:
            self._run_spec(spec)
        normal = [i for i in decoding
                  if self._slots[i] is not None and i not in set(spec)]
        return (self._prepare_step(normal) if normal else None), \
            not speculates

    def _step_args(self, live_idx) -> np.ndarray:
        """Everything one joint decode step takes after its weights:
        the int32 words of one ``_step_row`` record a slot (host).  A
        dead slot reads zeros (``top_p`` 1) beside its page-table row."""
        rows = np.zeros(self._cache.config.num_slots, self._step_row)
        rows["top_p"] = 1.0
        rows["pages"] = self._cache.page_table
        # one view a field: a scalar store into it costs a quarter of
        # one through the record
        tokens, positions, flags, counters, base_keys, temp, top_k, top_p \
            = (rows[name] for name in (
                "token", "position", "flags", "counter", "key",
                "temperature", "top_k", "top_p"))
        for i in live_idx:
            st = self._slots[i]
            # with a step of this slot in flight (``ahead``) the token
            # is that step's, on the device, and everything the host
            # counts is one further on than its books
            tokens[i] = st.last_token
            positions[i] = self._cache.lengths[i] + st.ahead
            # cache-hit first step: the shared pages already hold this
            # position's K/V — re-deriving it writes identical bytes,
            # but shared pages are immutable, so the write aims at trash
            flags[i] = _LIVE | (_TRASH if st.write_trash_once else 0) \
                | (_CARRY if st.ahead else 0)
            counters[i] = st.n_generated + st.ahead
            base_keys[i] = st.base_key
            temp[i] = st.req.temperature
            top_k[i] = st.req.top_k
            top_p[i] = st.req.top_p
        if live_idx:
            # how much of the table the kernel's walk is this step, a
            # layer: the blocks holding a position any slot attends (a
            # dead slot's one included) against every block there is
            stat_add("decode_attn_blocks_live", int(
                (positions // self._attn_block + 1).sum()))
            stat_add("decode_attn_blocks_walked", self._attn_table_blocks)
            if self._cache.config.latent:
                # a layer's: the rows of latents the live slots attend,
                # and the blocks the latent kernel walks for them
                live = positions[list(live_idx)]
                stat_add("decode_latent_positions_live",
                         int((live + 1).sum()))
                stat_add("decode_latent_blocks_walked",
                         int((live // self._attn_block + 1).sum()))
            # what the sampler's conditionals take this step (a dead
            # slot's knobs are the zeros above: no mask): the draw when
            # a slot samples, the vocabulary's sort when such a slot
            # also filters
            drawing = temp > 0.0
            if drawing.any():
                stat_add("decode_steps_drawn")
                if (drawing & ((top_k > 0) | (top_p < 1.0))).any():
                    stat_add("decode_steps_filtered")
            if self._window is not None:
                self._count_window(positions[list(live_idx)])
            if self._index is not None:
                # a layer's: the cached keys the live slots' queries
                # score, and the positions they attend of them
                n = positions[list(live_idx)] + 1
                stat_add("decode_index_positions_scored", int(n.sum()))
                stat_add("decode_index_positions_selected", int(
                    np.minimum(n, self.model.index_topk).sum()))
        return _words(rows)

    def _prefill_walks(self, t_pad: int):
        """[(layers, window, walk)] a kind of layer that has keys: how a
        whole-prompt prefill at bucket ``t_pad`` runs its attention
        (``prefill_walk``: the flash kernel's blocks or the plain form's
        span; the bucket where a model with a head a K/V head stays
        bitwise with its cached prefix)."""
        from ..ops.pallas_decode_attention import prefill_walk

        m, cc, w = self.model, self._cache.config, self._window
        walks = []
        n_full = layers_of_kind(m, "attention")
        if n_full:
            grouped = per_slot_kinds(m) or m.num_heads != cc.num_heads
            # the heads a prompt is attended in, where they are not the
            # cached rows' (a latent model's expanded form)
            kv_heads, d, dv = getattr(m, "prompt_heads", (
                cc.num_heads, cc.head_dim, cc.v_head_dim))
            walks.append((n_full, None, prefill_walk(
                t_pad, m.num_heads, kv_heads, d, dv, None,
                self.config.use_pallas)
                if grouped else ("blocks", t_pad, t_pad)))
        if w is not None:
            walks.append((w.num_layers, w.window, prefill_walk(
                t_pad, m.num_heads, w.num_heads, w.head_dim, w.v_head_dim,
                w.window, self.config.use_pallas)))
        return walks

    def _prefill_keys(self, t_pad: int, n: int):
        """(keys the softmaxes of a whole-prompt prefill cover, keys its
        ``n`` prompt rows can see, {form: layer-calls}) a head, over the
        model's layers that have keys.  Covered: what the form that
        runs walks (``_prefill_walks``: the flash kernel's visited
        blocks, else every row of the bucket times its block's span).
        Seen: the causal triangle, in a window layer its window's last
        positions."""
        from ..ops.pallas_decode_attention import prefill_keys_walked

        attended = live = 0
        forms = {}
        for layers, window, walk in self._prefill_walks(t_pad):
            attended += layers * prefill_keys_walked(t_pad, n, walk, window)
            # what caps the keys a row sees: a window, or the count an
            # indexer selects (the kernel still walks the triangle)
            cap = window if window is not None or self._index is None \
                else self.model.index_topk
            w = n if cap is None else min(cap, n)
            live += layers * (w * (w + 1) // 2 + (n - w) * w)
            forms[walk[0]] = forms.get(walk[0], 0) + layers
        return attended, live, forms

    def _count_window(self, positions) -> None:
        """A joint step's window-layer counters from the live slots'
        positions: what the window kernel walks and attends (a layer),
        and what the rings hold and recycle (all window layers)."""
        w, page = self._window, self._cache.config.page_size
        n = positions + 1                       # positions attended
        first = np.maximum(n - w.window, 0)
        stat_add("decode_window_blocks_walked", int(
            (positions // self._window_block
             - first // self._window_block + 1).sum()))
        stat_add("decode_window_positions_live", int(
            np.minimum(n, w.window).sum()))
        # whether the window caps anything: the live rows, and those of
        # them that are past it
        stat_add("decode_window_rows", len(n))
        stat_add("decode_window_rows_capped", int((n > w.window).sum()))
        # a token that opens a page the ring has already been round
        # once overwrites the page that slid out of the window
        stat_add("decode_window_pages_recycled", w.num_layers * int(
            ((positions % page == 0)
             & (positions // page >= self._ring)).sum()))
        stat_set("decode_window_pages_held",
                 self._cache.window_pages_held())

    def _lower(self, fn, operands, sharding):
        """``fn`` (the step, a whole-prompt prefill) lowered at this
        engine's own shapes, ``operands`` what it takes after the
        weights; nothing runs."""
        import jax

        args = (tuple(self._scope.get_var(n) for n in self._state_vars),
                self.weights) + tuple(operands)
        if sharding is not None:
            args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sharding), args)
        return fn.lower(*args)

    def lower_step(self, sharding=None):
        """The joint decode step lowered at this engine's own shapes;
        nothing runs.  ``.as_text()`` shows whether the Pallas kernel is
        in it (``tpu_custom_call``), ``.compile()`` asks the compiler.
        ``sharding`` re-targets every operand (e.g. to one device of a
        described, unattached topology) by lowering from shapes."""
        return self._lower(self._step_fn,
                           (self._step_args(()), self._no_tokens), sharding)

    def lower_prefill(self, t_pad: int, sharding=None):
        """The whole-prompt prefill of bucket ``t_pad``, as
        ``lower_step``."""
        return self._lower(self._prefill_fn(t_pad),
                           (self._prefill_args(t_pad, (0,)),), sharding)

    def _prepare_step(self, live_idx):
        """The joint step of ``live_idx`` up to its uploaded arguments.
        Nothing between here and ``_dispatch_step`` touches a slot that
        decodes: an admission claims pages no live slot owns, and a
        slot it fills reads as dead in these arguments."""
        attrs = {"iter": self._iter, "step": self._decode_steps,
                 "live": len(live_idx)}
        self._decode_steps += 1   # the ordinal is the hand-over's
        # copy-on-write any shared page this step would write (a
        # borrowed partial tail at its first divergent token)
        with otrace.span("serving/step_cow", **attrs):
            for i in live_idx:
                st = self._slots[i]
                if not st.write_trash_once:
                    self._perform_cow(i, self._cache.plan_cow(
                        i, [int(self._cache.lengths[i]) + st.ahead]))
        with otrace.span("serving/step_args", **attrs):
            up = _Uploads()
            args = (self.weights, up(self._step_args(live_idx)),
                    self._step_tokens)
            up.record()
        return live_idx, attrs, args

    def _dispatch_step(self, live_idx, attrs, args, ahead=0):
        """Hand the prepared joint step to the device, behind the joint
        step in flight (if any) and the ``ahead`` whole-prompt prefills
        of its iteration; returns the half that reads its tokens and
        delivers them, which the loop calls an iteration later."""
        t0 = time.monotonic()
        in_flight = int(self._flying is not None)
        # the period's other half where the loop runs serially: the
        # last step's tokens on the host until a hand-over with nothing
        # in flight (behind a step in flight the device waits for
        # nothing the host does here); observed behind this step's own
        # observation, so that nothing new lies inside
        # ``decode_step_seconds``
        turnaround = None if self._t_tokens is None or in_flight \
            else t0 - self._t_tokens
        self._t_tokens = None
        # the requests these rows are computed for: a slot may be
        # released, and filled again, before they are delivered
        states = [self._slots[i] for i in live_idx]

        def failed(e):  # fail the batch loudly, free every slot that
            # still holds its request, keep the consumer thread alive
            stat_add("decode_step_errors")
            for i, st in zip(live_idx, states):
                if self._slots[i] is st:
                    self._finish_slot(i, e)

        try:
            with otrace.span("serving/step_dispatch", in_flight=in_flight,
                             **attrs):
                nxt, logits, *recorded = self._exe.run_persistent(
                    self._step_fn, self._state_vars, args=args,
                    scope=self._scope)
                recorded = recorded[0] if recorded else {}
                t1 = time.monotonic()
        except Exception as e:  # noqa: BLE001
            failed(e)
            return lambda: None
        self._step_tokens = nxt
        stat_add("decode_steps_ahead", in_flight)
        for st in states:
            st.ahead += 1
            st.write_trash_once = False

        def finish():
            # the step's number stays, the iteration is the one that reads
            leaf = {**attrs, "iter": self._iter}
            try:
                with otrace.span("serving/step_sync", **leaf):
                    t2 = time.monotonic()
                    tokens = np.asarray(nxt)  # THE per-step sync point
            except Exception as e:  # noqa: BLE001
                if self._step_tokens is nxt:
                    # no later step may carry what could not be read
                    self._step_tokens = self._no_tokens
                failed(e)
                return
            t3 = self._t_tokens = time.monotonic()
            stat_time("decode_step_seconds", t3 - t0)
            with _Delivery(self, "serving/step_deliver", leaf):
                if turnaround is not None:
                    stat_time("decode_turnaround_seconds", turnaround)
                # the program's first run loads or compiles it: exempt
                if (t1 - t0) + (t3 - t2) > SLOW_STEP_S and self._step_ran:
                    self._record_slow_step(attrs, ahead,
                                           (t0, t1, t2, t3))
                self._step_ran = True
                # behind the slots' tokens, the model's counters of
                # this step (a model with ``layer_kinds``)
                for name, n in zip(self._tallies,
                                   tokens[len(self._slots):]):
                    stat_add(name, int(n))
                logits_np = recorded_np = None
                discarded = 0
                for i, st in zip(live_idx, states):
                    if self._slots[i] is not st:
                        # released while the step was in flight (an end
                        # token a step before, a reap, an abort, a
                        # failed batch), perhaps filled again since:
                        # the row is nobody's
                        discarded += 1
                        continue
                    st.ahead -= 1
                    if st.spec:
                        st.draft_lag += 1  # target-only write: draft stale
                    self._cache.lengths[i] += 1
                    if st.req.record_logits:
                        if logits_np is None:
                            logits_np = np.asarray(logits)
                            recorded_np = {n: np.asarray(v)
                                           for n, v in recorded.items()}
                        st.req.logits_trace.append(logits_np[i].copy())
                        for name, rows in recorded_np.items():
                            st.req.records.setdefault(name, []).append(
                                rows[i].copy())
                    self._deliver(i, int(tokens[i]))
                if discarded:
                    stat_add("decode_rows_discarded", discarded)
                stat_set("decode_slot_occupancy", self.live_slots)
                stat_add("decode_steps")

        return finish

    def _record_slow_step(self, attrs, ahead, stamps):
        """A joint step whose hand-over and read-back together took the
        host longer than ``SLOW_STEP_S``: how often, in which phase
        (whether the device or the wake-up was late no host clock can
        say), behind how many prefills, and what its process had compiled
        by then (programs born, how many of them cold, how long ago the
        newest): the stall's one lead is a cold set-up."""
        from ..observe import flight as _flight
        from ..observe import xla_stats

        stat_add("decode_steps_slow")
        t0, t1, t2, t3 = stamps
        born = xla_stats.births_summary()
        _flight.record(
            "serving/slow_step", name=self.name, iter=attrs["iter"],
            step=attrs["step"], live=attrs["live"], prefills_ahead=ahead,
            seconds=round((t1 - t0) + (t3 - t2), 6), t_handover_begin=t0,
            t_handover_end=t1, t_readback_begin=t2, t_readback_end=t3,
            births=born["births"], cache_misses=born["cache_misses"],
            since_last_birth_s=born["since_last_birth_s"])

    def _run_spec(self, spec_idx):
        """One speculative round for the greedy slots: a k-token draft
        burst (ONE dispatch) then ONE batched target step verifying all
        k+1 positions.  Every emitted token is the TARGET's argmax at
        its position — bitwise-identical to non-speculative greedy
        decode; proposals only decide how many tokens this round
        yields (1..k+1)."""
        c = self._cache.config
        s = c.num_slots
        k = self.config.spec_k
        rows = k + 1
        k_live = {}
        attrs = {"iter": self._iter, "step": self._decode_steps,
                 "live": len(spec_idx), "k": k}
        with otrace.span("serving/step_cow", **attrs):
            for i in spec_idx:
                st = self._slots[i]
                rem = st.req.max_new_tokens - st.n_generated
                k_live[i] = min(k, rem - 1)
                # CoW the pages this round's window writes (skip the
                # trash-aimed first position on the cache-hit path)
                n = int(self._cache.lengths[i])
                lo = n + (1 if st.write_trash_once else 0)
                self._perform_cow(i, self._cache.plan_cow(
                    i, range(lo, n + k_live[i] + 1)))
        with otrace.span("serving/step_args", **attrs):
            up = _Uploads()
            tok0 = np.zeros((s,), np.int32)
            start = np.zeros((s,), np.int32)
            live = np.zeros((s,), bool)
            trash_first = np.zeros((s,), bool)
            for i in spec_idx:
                st = self._slots[i]
                tok0[i] = st.last_token
                start[i] = self._cache.lengths[i]
                live[i] = True
                trash_first[i] = st.write_trash_once
            propose_args = (self.draft_weights, up(tok0), up(start),
                            up(live), up(trash_first),
                            up(self._cache.page_table))
            up.record()
        t0 = time.monotonic()
        try:
            if self._propose_fn is None:
                self._propose_fn = self._build_propose_fn(k)
            # the round is two dispatches, each with its own phases:
            # the draft burst, then the target's verify of its proposals
            with otrace.span("serving/step_dispatch", in_flight=0,
                             **attrs):
                (props,) = self._exe.run_persistent(
                    self._propose_fn, self._draft_state_vars,
                    args=propose_args, scope=self._scope)
            with otrace.span("serving/step_sync", **attrs):
                props = np.asarray(props)            # [S, k+1]
            with otrace.span("serving/step_args", **attrs):
                up = _Uploads()
                tokens = np.zeros((s, rows), np.int32)
                write_page = np.zeros((s, rows), np.int32)
                write_off = np.zeros((s, rows), np.int32)
                for i in spec_idx:
                    tokens[i, 0] = tok0[i]
                    tokens[i, 1:] = props[i, :k]
                    for r in range(k_live[i] + 1):
                        if r == 0 and trash_first[i]:
                            continue  # stays (0, 0): trash
                        pos = int(start[i]) + r
                        write_page[i, r] = self._cache.page_table[i][
                            pos // c.page_size]
                        write_off[i, r] = pos % c.page_size
                verify_args = (
                    self.weights, up(tokens), up(start),
                    up.host(np.zeros((s,), np.int32)),
                    up(self._cache.page_table), up(write_page),
                    up(write_off),
                    up.host(np.zeros((s, 2), np.uint32)),
                    up.host(np.zeros((s,), np.int32)),
                    up.host(np.zeros((s,), np.float32)),
                    up.host(np.zeros((s,), np.int32)),
                    up.host(np.ones((s,), np.float32)))
                up.record()
            with otrace.span("serving/step_dispatch", in_flight=0,
                             **attrs):
                _tok, greedy, logits = self._exe.run_persistent(
                    self._rows_fn(rows, s), self._state_vars,
                    args=verify_args, scope=self._scope)
            with otrace.span("serving/step_sync", **attrs):
                greedy = np.asarray(greedy)          # [S, k+1]
        except Exception as e:  # noqa: BLE001 — batch fault isolation
            stat_add("decode_step_errors")
            for i in spec_idx:
                if self._slots[i] is not None:
                    self._finish_slot(i, e)
            return
        stat_time("decode_step_seconds", time.monotonic() - t0)
        self._decode_steps += 1
        logits_np = None
        proposed = accepted = 0
        with _Delivery(self, "serving/step_deliver", attrs):
            for i in spec_idx:
                st = self._slots[i]
                a = 0
                while a < k_live[i] and \
                        int(props[i, a]) == int(greedy[i, a]):
                    a += 1
                proposed += k_live[i]
                accepted += a
                self._tev(st.req, "spec_round", slot=i,
                          proposed=k_live[i], accepted=a)
                st.write_trash_once = False
                for j in range(a + 1):
                    self._cache.lengths[i] += 1
                    if st.req.record_logits:
                        if logits_np is None:
                            logits_np = np.asarray(logits)
                        st.req.logits_trace.append(
                            logits_np[i, j].copy())
                    self._deliver(i, int(greedy[i, j]))
                    if self._slots[i] is None:
                        break  # finished (EOS/budget) mid-emission
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        stat_add("decode_spec_proposed", proposed)
        stat_add("decode_spec_accepted", accepted)
        stat_add("decode_spec_rounds")
        total = stat_get("decode_spec_proposed")
        if total:
            acc = stat_get("decode_spec_accepted")
            # deprecated integer-percent + float-precision _ppm
            stat_set("spec_accept_rate", int(100 * acc / total))
            stat_set("spec_accept_rate_ppm", int(1e6 * acc / total))
        stat_set("decode_slot_occupancy", self.live_slots)

    # -- oracle / observability ------------------------------------------
    def recompute_logits(self, tokens: Sequence[int],
                         quantized: Optional[bool] = None) -> np.ndarray:
        """Full-recompute oracle: run the ENTIRE sequence through the
        prefill path from scratch (no cache reuse, no prefix sharing)
        and return the last position's logits.  Runs on THROWAWAY page
        pools — the prefill body only ever WRITES pages (its attention
        reads the locally built K/V, so fresh zero pools are
        numerically identical), and touching the live pools would race
        the engine thread's donating step.  Safe to call while the
        engine is serving.

        ``quantized`` defaults to False: the oracle is the FULL-
        PRECISION reference, which on a kv-quantized engine is what the
        quality-delta accounting compares against.  Pass
        ``quantized=True`` on a quantized engine for the quantized
        self-oracle — the recompute through the same per-position
        quant-dequant the cache stores, which the composition tests pin
        BITWISE against streamed decode.  ``tests/test_decode_engine.py``
        compares the default oracle bitwise on unquantized engines;
        ``tests/test_decode_prefix_spec.py`` does the same for the
        shared-prefix, CoW, chunked, and speculative paths."""
        import jax.numpy as jnp

        qz = bool(quantized) if quantized is not None else False
        tokens = [int(t) for t in tokens]
        t_pad = self._buckets.seq_bucket(len(tokens))
        cc = self._cache.config
        shape, vshape = cc.pool_shape(), cc.pool_shape(
            row_lanes=cc.v_row_lanes)
        if qz:
            sshape = cc.pool_shape(row_lanes=cc.num_heads)
            scratch = (jnp.zeros(shape, jnp.int8),
                       jnp.zeros(vshape, jnp.int8),
                       jnp.full(sshape, kv_cache.SCALE_EPS,
                                cc.scale_dtype),
                       jnp.full(sshape, kv_cache.SCALE_EPS,
                                cc.scale_dtype))
        elif not cc.v_row_lanes:        # a latent or a joint cache
            scratch = (jnp.zeros(shape, cc.dtype),)
        else:
            scratch = (jnp.zeros(shape, cc.dtype),
                       jnp.zeros(vshape, cc.dtype))
        if self._window is not None:
            # slot 0's ring is all a throwaway prefill writes
            scratch += tuple(
                jnp.zeros(wshape, cc.dtype) for wshape in
                self._window.pool_shapes(1, cc.page_size))
        if self._cache.recurrent is not None:
            # one slot's worth of throwaway state rows
            rec = self._cache.recurrent
            scratch += tuple(
                jnp.zeros((1,) + rshape, rdtype)
                for _ in range(rec.num_layers)
                for rshape, rdtype in rec.arrays.values())
        (_tok, last, *_), _ = self._prefill_fn(t_pad, quantized=qz)(
            scratch, self.weights, self._prefill_args(t_pad, tokens))
        return np.asarray(last)

    def debug_requests(self) -> List[dict]:
        """Live in-flight table (the ``/debug/requests`` route): one
        row per occupied slot and per queued request — trace id, age,
        slot, phase, pages held, prefill chunks done, tokens emitted,
        deadline headroom.  Read-mostly and engine-thread-racy by
        design (a scrape must never block the step loop); a row for a
        slot that frees mid-snapshot simply disappears next scrape."""
        now = time.monotonic()
        rows: List[dict] = []
        for i, st in enumerate(list(self._slots)):
            if st is None:
                continue
            req = st.req
            rows.append({
                "trace_id": req.trace.trace_id
                if req.trace is not None else None,
                "replica": self.name,
                "slot": i,
                "phase": st.phase,
                "age_ms": round((now - req.t_enqueue) * 1e3, 3),
                "prompt_len": len(req.prompt),
                "prefill_pos": st.prefill_pos,
                "chunks_done": st.chunks,
                "pages": len(self._cache.slot_pages(i)),
                "tokens": st.n_generated,
                "max_new_tokens": req.max_new_tokens,
                "speculative": st.spec,
                "deadline_in_ms": None if req.deadline is None
                else round((req.deadline - now) * 1e3, 3),
            })
        with self._cond:
            queued = list(self._queue)
        for req in queued:
            if req.done():
                continue
            rows.append({
                "trace_id": req.trace.trace_id
                if req.trace is not None else None,
                "replica": self.name,
                "slot": None,
                "phase": "queued",
                "age_ms": round((now - req.t_enqueue) * 1e3, 3),
                "prompt_len": len(req.prompt),
                "tokens": 0,
                "max_new_tokens": req.max_new_tokens,
                "deadline_in_ms": None if req.deadline is None
                else round((req.deadline - now) * 1e3, 3),
            })
        return rows

    def stats(self) -> dict:
        with self._cond:
            depth = len(self._queue)
        hp, pp = self._hit_pages, self._prompt_pages
        sp, sa = self._spec_proposed, self._spec_accepted
        return {
            "name": self.name,
            "slots": self.config.slots,
            "live_slots": self.live_slots,
            "free_slots": self.free_slots,
            "queue_depth": depth,
            "tokens_total": self.tokens_total,
            "free_pages": self._cache.allocator.num_free,
            "num_pages": self._cache.config.num_pages,
            "cache_bytes": self._cache.config.cache_bytes(),
            "prefix_cache": self.config.prefix_cache,
            "kv_quant": self.config.kv_quant,
            "page_bytes": self._cache.config.page_bytes(),
            "prefix_hit_pages": hp,
            "prefix_prompt_pages": pp,
            "cache_hit_rate": round(hp / pp, 4) if pp else 0.0,
            "shared_pages": self._cache.shared_pages,
            "cow_copies": self._cow_copies,
            "prefill_chunks": self._prefill_chunk_count,
            "prefill_pad_waste": stat_get("prefill_pad_waste") / 1e6,
            "spec_enabled": self.spec_enabled,
            "spec_proposed": sp,
            "spec_accepted": sa,
            "spec_accept_rate": round(sa / sp, 4) if sp else 0.0,
        }
