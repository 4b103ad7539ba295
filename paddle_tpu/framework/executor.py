"""Executor: compiles whole program blocks to single XLA computations.

Role parity: reference Executor (paddle/fluid/framework/executor.cc:180,
python/paddle/fluid/executor.py:913) — same ``run(program, feed,
fetch_list)`` contract.  TPU-native redesign (SURVEY.md §7): instead of the
reference's per-op interpreter hot loop (executor.cc:474-480, one scope
lookup + InferShape + kernel launch per op per step), the block is traced
ONCE through the lowering registry into a jax function

    (feeds, state, rng) -> (fetches, new_state, rng')

jitted with the state donated (in-place param update semantics), cached by
(program fingerprint, feed spec, fetch list, state spec).  Per-step cost is
one XLA executable launch; scheduling/fusion/memory are XLA's job (this
collapses the reference's ParallelExecutor/SSA-graph machinery,
parallel_executor.cc:504).

Pipelined dispatch (FLAGS_max_inflight_steps, default 2): ``run`` returns
a lazy :class:`StepHandle` instead of forcing a device→host sync per
step; up to N steps stay in flight and dispatch backpressures by
draining the oldest.  NaN-scan, FLAGS_benchmark sync, and StepTimer
accounting happen at window-drain points (``Executor.drain``, handle
reads, backpressure, ``close``, checkpoint snapshots) so telemetry only
ever reflects completed steps.  ``FLAGS_max_inflight_steps=0`` restores
the legacy synchronous fetch path.
"""
from __future__ import annotations

import collections
import logging
import os
import threading
import weakref
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import dtypes
from .lowering import PSEUDO_OPS, LoweringContext, get_lowering
from .place import CPUPlace, Place, _default_place
from .program import Program, Variable, default_main_program
from .scope import (PackedParamRef, Scope, StackedParamRef, global_scope,
                    is_device_array as _is_device_array)

logger = logging.getLogger(__name__)

RNG_VAR = "@RNG_KEY@"
NAN_FLAGS_VAR = "@NAN_FLAGS@"

# ops executed host-side by an interpretive walk (file I/O cannot live
# inside a compiled XLA computation); reference runs these through the
# same C++ executor hot loop (save_op.cc:85, load_op.cc:67)
HOST_OPS = {"save", "load", "save_combine", "load_combine"}


def _make_scan_fn(step_fn, state_mut, state_const, state_out, feed_names,
                  scan_steps):
    """Wrap a single-step `step_fn(env, rng) -> (fetches, new_rng)` into the
    K-step lax.scan harness shared by the single-device and sharded paths.

    scan_steps=None: feeds are stacked with a leading step dim (scan xs).
    scan_steps=K: single-step feeds reused every iteration (xs=None).
    Write-only persistent outputs (not read back each step) are stacked and
    the last step's value wins.
    """
    from jax import lax

    mut_set = set(state_mut)
    write_only = tuple(n for n in state_out if n not in mut_set)

    def fn(feed_stacks, mut_vals, const_vals, rng):
        def body(carry, xs):
            mut, key = carry
            env = {}
            env.update(zip(state_mut, mut))
            env.update(zip(state_const, const_vals))
            env.update(zip(feed_names, feed_stacks if xs is None else xs))
            fetches, new_key = step_fn(env, key)
            wo = tuple(env[n] for n in write_only)
            new_mut = tuple(env[n] for n in state_mut)
            return (new_mut, new_key), (fetches, wo)

        xs = None if scan_steps is not None else feed_stacks
        (final_mut, final_rng), (fetch_stacks, wo_stacks) = lax.scan(
            body, (mut_vals, rng), xs, length=scan_steps)
        final = dict(zip(state_mut, final_mut))
        final.update({n: s[-1] for n, s in zip(write_only, wo_stacks)})
        new_state = tuple(final[n] for n in state_out)
        return fetch_stacks, new_state, final_rng

    return fn


@dataclass
class _Compiled:
    fn: object
    feed_names: Tuple[str, ...]
    state_mut: Tuple[str, ...]  # read & overwritten -> donated buffers
    state_const: Tuple[str, ...]  # read-only state
    state_out: Tuple[str, ...]
    fetch_names: Tuple[str, ...]
    uses_rng: bool
    # multi-process SPMD: converts process-local feed/state values into
    # global jax.Arrays over the mesh before the executable call
    globalize: object = None
    # FLAGS_check_nan_inf: (op type, build site) per scanned op, parallel
    # to the extra NAN_FLAGS fetch; nan_scan records that the sentinel
    # fetch was appended even when the op list is empty
    nan_ops: Tuple = ()
    nan_scan: bool = False
    # pipeline v3: PackPlan sharding params+opt state per stage; run()
    # calls its ensure_packed before assembling the state tuple
    pipeline_pack: object = None
    n_calls: int = 0
    # step telemetry (observe/step_stats.py): static per-step FLOPs
    # (hapi/model_stat.py accounting) and allreduce payload bytes
    flops_per_step: float = 0.0
    allreduce_bytes: int = 0
    # XLA introspection (observe/xla_stats.py): the raw jax.jit callable
    # for the AOT lower+compile at first dispatch, and the device the
    # mesh-less path pins execution to (None when a mesh owns placement)
    jit_fn: object = None
    jit_device: object = None
    # step-phase attribution (observe/phases.py): the compile-time cost
    # model — predicted compute seconds + per-collective exposed/hidden
    # ledger — consulted at each window drain; None when the plane is
    # off or the model could not price this program
    phase_plan: object = None


class _InflightStep:
    """One dispatched-but-not-yet-synced executor step in the window."""

    __slots__ = ("sync_refs", "nan_flags", "nan_ops", "t_dispatch",
                 "steps", "examples", "compiled", "flops_per_step",
                 "allreduce_bytes", "host_s", "phase_plan", "drained")

    def __init__(self, sync_refs, nan_flags, nan_ops, t_dispatch, steps,
                 examples, compiled, flops_per_step, allreduce_bytes,
                 host_s=0.0, phase_plan=None):
        self.sync_refs = sync_refs          # fetch device arrays (never
        self.nan_flags = nan_flags          # donated, safe to hold)
        self.nan_ops = nan_ops
        self.t_dispatch = t_dispatch
        self.steps = steps
        self.examples = examples
        self.compiled = compiled
        self.flops_per_step = flops_per_step
        self.allreduce_bytes = allreduce_bytes
        # phase attribution (observe/phases.py): dispatch-side host
        # seconds (pass pipeline + analysis + feed prep, backpressure
        # excluded) and the entry's compile-time cost model
        self.host_s = host_s
        self.phase_plan = phase_plan
        self.drained = False


class _InflightWindow:
    """Bounded FIFO of in-flight pipelined steps (FLAGS_max_inflight_steps).

    Dispatch pushes; ``backpressure`` drains the oldest entries until the
    window is under the cap, so ahead-of-device Python can never pile up
    unbounded live fetch buffers.  A drain is the truth point moved out
    of the dispatch path: it blocks until the step's fetches are ready
    (``fetch_sync_seconds`` histogram + ``dispatch/drain`` span), feeds
    the StepTimer with the inter-drain wall time (== real per-step loop
    time in steady state), checks the NaN-scan flags, and updates the
    ``executor_inflight_steps`` gauge.  Entries hold only fetch buffers —
    never scope state, which a later step may donate."""

    def __init__(self):
        self._entries = collections.deque()
        self._lock = threading.RLock()
        self._last_drain: Optional[float] = None
        # a drain failure (XLA runtime error, NaN-scan raise) that was
        # hit on a NON-raising path (StepTimer.summary's telemetry
        # drain) is parked here and re-raised at the next raising drain
        # point — a drained entry is popped, so without this the error
        # would be consumed forever
        self._failed: Optional[BaseException] = None

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def push(self, entry: _InflightStep):
        with self._lock:
            self._entries.append(entry)
        _update_inflight_gauge()

    def _raise_pending(self):
        if self._failed is not None:
            e, self._failed = self._failed, None
            raise e

    def backpressure(self, cap: int):
        """Block until fewer than ``cap`` steps are in flight."""
        with self._lock:
            self._raise_pending()
            while len(self._entries) >= max(cap, 1):
                self._drain_oldest()

    def drain_through(self, entry: _InflightStep):
        """Drain (in order) every entry up to and including ``entry``."""
        with self._lock:
            self._raise_pending()
            while not entry.drained and self._entries:
                self._drain_oldest()

    def drain_all(self, raise_errors: bool = True):
        """Drain everything.  ``raise_errors=False`` (the telemetry
        read path) parks a drain failure in ``_failed`` instead of
        raising, so the error is delivered at the next raising drain
        point rather than swallowed."""
        with self._lock:
            if raise_errors:
                self._raise_pending()
            while self._entries:
                self._drain_oldest(raise_errors=raise_errors)

    def _drain_oldest(self, raise_errors: bool = True):
        import time as _time

        import jax

        from ..monitor import stat_add
        from ..observe import flight as _flight
        from ..observe import step_stats as _step_stats
        from ..observe import tracer as otrace
        from ..observe.histogram import stat_time

        # the entry stays IN the deque while its drain blocks (popped in
        # the finally): a hung device call is then visible to the stall
        # watchdog's lock-free sample (observe/health.py) as a live
        # window entry whose age keeps growing — popping first would
        # make the one step that matters invisible mid-hang
        e = self._entries[0]
        try:
            t0 = _time.perf_counter()
            try:
                with otrace.span("dispatch/drain", steps=e.steps,
                                 n=len(e.sync_refs)):
                    jax.block_until_ready(e.sync_refs)
                    if e.nan_flags is not None:
                        jax.block_until_ready(e.nan_flags)
            except BaseException as err:
                # a drain that RAISES is still progress (the process is
                # failing, not hung): advance the drained counter so the
                # stall watchdog never mistakes a delivered error for a
                # stall
                stat_add("executor_steps_drained", e.steps)
                _flight.record("executor/drain_error", steps=e.steps,
                               error=f"{type(err).__name__}: {err}"[:500])
                if raise_errors:
                    raise
                if self._failed is None:
                    self._failed = err
                return
            stat_add("executor_steps_drained", e.steps)
        finally:
            self._entries.popleft()
            e.drained = True
            _update_inflight_gauge()
        now = _time.perf_counter()
        stat_time("fetch_sync_seconds", now - t0)
        # inter-drain wall time: in a steady pipelined loop drains are
        # forced by backpressure once per dispatch, so this IS the
        # training loop's per-step period (input wait included) — the
        # number that says how fast the LOOP is, not just the chip
        start = e.t_dispatch if self._last_drain is None \
            else max(self._last_drain, e.t_dispatch)
        self._last_drain = now
        _step_stats.step_timer().record_run(
            max(now - start, 0.0), steps=e.steps, examples=e.examples,
            compiled=e.compiled, flops_per_step=e.flops_per_step,
            allreduce_bytes_per_step=e.allreduce_bytes)
        # step-phase attribution + anomaly trigger (observe/phases.py,
        # observe/profiler_capture.py): the drain is THE truth point —
        # wall = inter-drain loop period, sync = this drain's block,
        # host = the dispatch-side host seconds carried on the entry
        from ..observe import phases as _phases
        from ..observe import profiler_capture as _prof

        _phases.on_step_drained(
            wall_s=max(now - start, 0.0), sync_s=now - t0, host_s=e.host_s,
            steps=e.steps, plan=e.phase_plan, compiled=e.compiled)
        _prof.on_step_drained(max(now - start, 0.0) / max(e.steps, 1),
                              compiled=e.compiled)
        if e.nan_flags is not None:
            try:
                _raise_on_nan(np.asarray(e.nan_flags), e.nan_ops)
            except BaseException as err:
                _flight.record("executor/nan_detected",
                               error=f"{err}"[:500])
                if raise_errors:
                    raise
                if self._failed is None:
                    self._failed = err


def _raise_on_nan(nan_flags, nan_ops):
    """Host-side check of the per-op finite flags fetched by the
    nan-scan (shared by the sync path and the window drain)."""
    nan_flags = nan_flags.astype(bool)
    if not nan_ops:
        return
    ok = nan_flags.reshape(-1, len(nan_ops)).all(axis=0)
    if not ok.all():
        i = int(np.argmin(ok))
        op_type, site = nan_ops[i]
        raise RuntimeError(
            f"FLAGS_check_nan_inf: op {op_type!r} (built at "
            f"{site}) produced NaN/Inf (op #{i} of the compiled "
            f"block)")


class StepHandle(list):
    """Lazy fetch list of one pipelined ``Executor.run``/``run_steps``.

    A ``list`` subclass so every existing consumer keeps working —
    indexing, iteration, unpacking, ``len`` — but the device→host sync
    is deferred: items start as jax device arrays and materialize on
    access.  With ``materialize=True`` (the ``run(return_numpy=True)``
    contract) ``handle[i]`` returns a cached ``np.ndarray``; reading any
    item first drains the executor's in-flight window through this step
    (telemetry + NaN-scan fire there).  ``numpy()`` materializes
    everything; ``block_until_ready()`` syncs without converting."""

    def __init__(self, fetches, window=None, entry=None, materialize=True):
        list.__init__(self, fetches)
        self._window = window
        self._entry = entry
        self._materialize = materialize

    def block_until_ready(self):
        """Wait for this step (and every older in-flight step) to
        complete on device; no host transfer."""
        if self._window is not None and self._entry is not None:
            self._window.drain_through(self._entry)
        else:
            import jax

            jax.block_until_ready([v for v in list.__iter__(self)
                                   if _is_jax_array(v)])
        return self

    def numpy(self):
        """Materialize every fetch to host numpy (the one sync point);
        returns a plain list."""
        from ..observe import tracer as otrace

        self.block_until_ready()
        with otrace.span("executor/fetch", n=list.__len__(self)):
            out = []
            for i in range(list.__len__(self)):
                v = list.__getitem__(self, i)
                if not isinstance(v, np.ndarray):
                    v = np.asarray(v)
                    if self._materialize:
                        list.__setitem__(self, i, v)
                out.append(v)
            return out

    def device_arrays(self):
        """The raw stored values, no sync (device arrays until the item
        has been materialized through access)."""
        return list(list.__iter__(self))

    def _resolve(self, i):
        v = list.__getitem__(self, i)
        if self._materialize and not isinstance(v, np.ndarray):
            from ..observe import tracer as otrace

            self.block_until_ready()
            with otrace.span("executor/fetch", n=1):
                v = np.asarray(v)
            list.__setitem__(self, i, v)
        return v

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return [self._resolve(i)
                    for i in range(*idx.indices(list.__len__(self)))]
        return self._resolve(idx)

    def __iter__(self):
        for i in range(list.__len__(self)):
            yield self._resolve(i)

    def __array__(self, dtype=None, copy=None):
        arr = np.asarray(self.numpy())
        return arr.astype(dtype) if dtype is not None else arr


# every constructed Executor, for the process-wide drain points (ckpt
# snapshot, StepTimer.summary): a checkpoint must capture a quiescent
# state and telemetry reads must reflect completed steps
_LIVE_EXECUTORS: "weakref.WeakSet[Executor]" = weakref.WeakSet()

# thread id -> perf_counter start of an in-flight FIRST executable call
# (trace + XLA compile).  Sampled lock-free by the stall watchdog
# (observe/health.py): a legitimate multi-minute compile must not read
# as a hung device step, so the watchdog scales its timeout while one
# is active (GIL-atomic dict set/del; telemetry only)
_ACTIVE_COMPILES: Dict[int, float] = {}


def _update_inflight_gauge():
    """executor_inflight_steps = TOTAL in-flight steps across every live
    Executor (a per-window write would make the single process gauge
    flap between unrelated executors).  Reads other windows' deque
    lengths without their locks: len() is GIL-atomic and this is a
    gauge, not an invariant."""
    from ..monitor import stat_set

    try:
        total = sum(len(exe._window._entries)
                    for exe in list(_LIVE_EXECUTORS))
    except RuntimeError:  # WeakSet mutated by a concurrent construction
        return            # telemetry only: the next push/drain re-writes
    stat_set("executor_inflight_steps", total)


def drain_all(raise_errors: bool = True):
    """Drain the in-flight window of every live Executor (the process-
    wide quiescence point: ckpt snapshots and telemetry summaries call
    this so they only ever observe completed steps).  With
    ``raise_errors=False`` (telemetry reads) a drain failure is parked
    on its window and re-raised at the next raising drain point instead
    of being lost."""
    for exe in list(_LIVE_EXECUTORS):
        exe._window.drain_all(raise_errors=raise_errors)


def quiesce_all(raise_errors: bool = True):
    """Process-wide quiescence for the elastic supervisor: drain every
    live Executor's in-flight window AND every pending async checkpoint
    save, so the next restore observes only completed steps and
    committed (or cleanly failed) checkpoints.  ``raise_errors=False``
    parks drain failures for the next raising drain point — a failed
    attempt's own exception is already being handled."""
    drain_all(raise_errors=raise_errors)
    try:
        from ..ckpt import wait_all as _ckpt_wait_all

        _ckpt_wait_all(raise_errors=raise_errors)
    except ImportError:  # pragma: no cover - partial installs
        pass


def close_all() -> int:
    """Re-init hook for topology changes: close every live Executor
    (drains its window, then drops all its compiled-program caches) so
    a rebuild on a NEW device mesh starts from a clean slate instead
    of reusing executables keyed to the dead topology.  Returns the
    number of executors closed."""
    n = 0
    for exe in list(_LIVE_EXECUTORS):
        try:
            exe.close()
        except Exception:  # noqa: BLE001 - a failing drain on a dying
            pass           # topology must not block the re-init
        _LIVE_EXECUTORS.discard(exe)
        n += 1
    _update_inflight_gauge()
    return n


# the one default location of jax's persistent compilation cache: a
# fixed path inside the checkout (the path is part of the cache key, so
# a directory that moves never hits); .gitignore lists it
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")
_compile_cache_applied = False


def default_compile_cache_dir(environ, backend: str) -> Optional[str]:
    """The directory this program points jax's persistent compilation
    cache at, or None where it sets nothing: the cache is placed from
    OUTSIDE when ``JAX_COMPILATION_CACHE_DIR`` is in the environment
    (jax has already taken it), and a process that selected the CPU
    keeps none (XLA:CPU reloads buy nothing and warn about machine
    features)."""
    if "JAX_COMPILATION_CACHE_DIR" in environ or backend == "cpu":
        return None
    return COMPILE_CACHE_DIR


def _maybe_enable_compile_cache():
    """Applied once, when the first Executor (and so the first serving
    engine) is built - never at import."""
    global _compile_cache_applied

    if _compile_cache_applied:
        return
    _compile_cache_applied = True
    import jax

    d = default_compile_cache_dir(os.environ, jax.default_backend())
    if d is not None:
        jax.config.update("jax_compilation_cache_dir", d)


def _block_written(program, block_idx: int) -> set:
    """All names written anywhere inside a block (incl. nested blocks)."""
    sub = program.blocks[block_idx]
    out: set = set()
    for sop in sub.ops:
        out.update(sop.output_arg_names())
        for aname in ("sub_block", "sub_block_t", "sub_block_f"):
            if sop.has_attr(aname):
                out |= _block_written(program, int(sop.attr(aname)))
    return out


def _ctrl_attr_reads(program, op) -> List[str]:
    """cond_pair branch-output names that are NOT produced inside the
    branch (a branch returning an unchanged outer var / captured const):
    the lowering reads them from the env, so they are external reads."""
    reads: List[str] = []
    if op.type == "cond_pair":
        for aname, sb in (("t_outs", "sub_block_t"),
                          ("f_outs", "sub_block_f")):
            written = _block_written(program, int(op.attr(sb)))
            for n in (op.attr(aname, []) or []):
                if n not in written:
                    reads.append(n)
    return reads


def _sub_external_reads(program, block_idx: int) -> List[str]:
    """Names a sub-block reads from its surroundings (closures for the
    lax.while_loop/lax.cond lowering)."""
    sub = program.blocks[block_idx]
    local_written: set = set()
    ext: List[str] = []
    for sop in sub.ops:
        for n in sop.input_arg_names() + _ctrl_attr_reads(program, sop):
            if n not in local_written and n not in ext:
                ext.append(n)
        for aname in ("sub_block", "sub_block_t", "sub_block_f"):
            if sop.has_attr(aname):
                for n in _sub_external_reads(program, int(sop.attr(aname))):
                    if n not in local_written and n not in ext:
                        ext.append(n)
        local_written.update(sop.output_arg_names())
    return ext


# ops whose effect is not visible through their outputs (p2p send/recv
# pairs match POSITIONALLY per ring, so dropping either end corrupts the
# pairing; barrier is a rendezvous; print emits a host debug callback) —
# the pass-pipeline DCE must never slice them away
SIDE_EFFECT_OPS = {"send_v2", "partial_send", "recv_v2", "partial_recv",
                   "barrier", "print"}

# communication ops: each lowering gets its own tracer span with
# payload bytes + dtype args (observe/tracer.py), and the allreduce
# subset feeds the StepTimer's bytes/step accounting
COLLECTIVE_OPS = {"c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
                  "c_allreduce_prod", "allreduce", "mp_allreduce_sum",
                  "c_broadcast", "c_allgather", "c_reducescatter",
                  "c_reduce_sum", "c_reduce_max", "c_reduce_min",
                  "c_scatter", "c_concat", "c_split", "c_shard_slice",
                  "send_v2", "partial_send", "recv_v2", "partial_recv",
                  "barrier"}
_ALLREDUCE_OPS = {"c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
                  "c_allreduce_prod", "allreduce", "mp_allreduce_sum"}


def _collective_span_args(env, op, mesh=None):
    """bytes/dtype args for a collective's tracer span, read off the
    traced input value (static shapes at trace time).

    Tensor-parallel programs (GSPMD path): a grad collective carrying
    the ShardingPropagationPass's ``__tp_spec__`` stamp reports the
    dp-axis payload its reduce actually moves — the mp-SHARD bytes,
    with an explicit ``axes`` arg — because the grad stays mp-sharded
    through its dp sum (the acceptance telemetry for "grad allreduce
    over the dp axis only")."""
    names = op.input_arg_names()
    v = env.get(names[0]) if names else None
    if v is None or not hasattr(v, "shape") or not hasattr(v, "dtype"):
        return {"var": names[0] if names else ""}
    n = 1
    for s in v.shape:
        n *= int(s)
    nbytes = n * np.dtype(v.dtype).itemsize
    args = {"bytes": nbytes, "dtype": str(v.dtype),
            "var": names[0] if names else ""}
    from .passes import TP_SPEC_ATTR

    tp_spec = op.attr(TP_SPEC_ATTR, None)
    if tp_spec and mesh is not None and "mp" in mesh.axis_names:
        if "mp" in str(tp_spec).split(","):
            args["bytes"] = nbytes // int(mesh.shape["mp"])
        args["axes"] = "dp"
        args["tp_spec"] = str(tp_spec)
    return args


def _program_allreduce_bytes(block, op_list) -> int:
    """Static allreduce payload per step, from the post-pass op stream
    (so fused buckets count once at their coalesced size).  A
    LayerScanPass-stacked collective moves ``__layer_stack__`` x its
    var's declared per-layer bytes — the stack axis is a runtime
    artifact the var metadata does not carry."""
    from .passes import LAYER_STACK_ATTR

    total = 0
    for op in op_list:
        if op.type not in _ALLREDUCE_OPS:
            continue
        names = op.input_arg_names()
        var = block._find_var_recursive(names[0]) if names else None
        if var is None or not var.shape or any(int(s) <= 0 for s in var.shape):
            continue
        try:
            itemsize = np.dtype(dtypes.to_np(var.dtype)).itemsize
        except (KeyError, ValueError, TypeError):
            continue
        n = 1
        for s in var.shape:
            n *= int(s)
        total += n * itemsize * max(int(op.attr(LAYER_STACK_ATTR, 0) or 0), 1)
    return total


def _prune_ops(program, fetch_names, keep_side_effect_ops=False):
    """Backward slice: keep only ops whose outputs (transitively) feed the
    fetch list (reference framework/prune.h / Executor.run(use_prune)).
    An eval fetch on a training program thus skips backward+optimizer ops
    instead of silently advancing the parameters.

    ``keep_side_effect_ops`` (the pass-pipeline DCE caller) additionally
    keeps ops with no outputs and the SIDE_EFFECT_OPS unconditionally."""
    block = program.global_block
    needed = set(fetch_names)
    keep = []
    for op in reversed(block.ops):
        if op.type in PSEUDO_OPS:
            continue
        keep_this = bool(set(op.output_arg_names()) & needed)
        if keep_side_effect_ops and (
                op.type in SIDE_EFFECT_OPS or not op.output_arg_names()):
            keep_this = True
        if keep_this:
            keep.append(op)
            needed.update(op.input_arg_names())
            needed.update(_ctrl_attr_reads(program, op))
            for aname in ("sub_block", "sub_block_t", "sub_block_f"):
                if op.has_attr(aname):
                    needed.update(
                        _sub_external_reads(program, int(op.attr(aname))))
    keep.reverse()
    return keep


def _feed_spec(block, feed: Dict[str, np.ndarray]):
    spec = []
    arrays = {}
    for name in sorted(feed):
        val = feed[name]
        if not _is_jax_array(val):  # device arrays pass through untouched
            val = np.asarray(val)
            var = block._find_var_recursive(name)
            if var is not None and var.dtype:
                want = dtypes.to_np(var.dtype)
                if val.dtype != want:
                    val = val.astype(want)
        arrays[name] = val
        spec.append((name, tuple(val.shape), str(val.dtype)))
    return tuple(spec), arrays


class Executor:
    def __init__(self, place: Optional[Place] = None, mesh=None):
        self.place = place if place is not None else _default_place()
        self._cache: Dict[tuple, _Compiled] = {}
        # (program fingerprint, feed names, scope id) -> (state_in, state_out)
        self._analysis_cache: Dict[tuple, tuple] = {}
        # (program fingerprint, fetch names) -> pruned op list
        self._prune_cache: Dict[tuple, list] = {}
        # (program fingerprint, pass config, fetch/feed names, scope) ->
        # pass-rewritten program (or the original when no pass applied)
        self._pass_cache: Dict[tuple, Program] = {}
        self._mesh = mesh  # explicit mesh wins over the global parallel env
        # pipelined dispatch (FLAGS_max_inflight_steps): the bounded
        # window of dispatched-but-unsynced steps owned by this executor
        self._window = _InflightWindow()
        _LIVE_EXECUTORS.add(self)
        _maybe_enable_compile_cache()
        # flight recorder + health plane (observe/): the run-metadata
        # event fires once per process, executor creation is a
        # lifecycle event, and FLAGS_stall_timeout_s > 0 arms the stall
        # watchdog — all ~zero cost when the flags are off
        from ..observe import flight as _flight
        from ..observe import health as _health

        _flight.record_run_metadata()
        _flight.record("executor/created",
                       place=type(self.place).__name__,
                       device_id=self.place.device_id)
        _health.maybe_start_watchdog()
        # continuous low-duty-cycle profiling (FLAGS_prof_continuous_s)
        from ..observe import profiler_capture as _prof

        _prof.maybe_start_continuous()

    def _active_mesh(self):
        if self._mesh is not None:
            return self._mesh
        try:
            from ..distributed.parallel_env import get_mesh

            return get_mesh()
        except ImportError:
            return None

    # ------------------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, np.ndarray]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,  # always cached; kept for API parity
        use_prune: bool = False,
    ):
        import jax

        program = program if program is not None else default_main_program()
        feed = dict(feed or {})
        scope = scope if scope is not None else global_scope()
        fetch_names = tuple(
            v.name if isinstance(v, Variable) else str(v) for v in (fetch_list or [])
        )

        block = program.global_block

        # host-side I/O programs (save/load ops write files; reference
        # save_op.cc:85/load_op.cc:67 run through the executor the same
        # way) are interpreted on host, never compiled
        if any(op.type in HOST_OPS for op in block.ops):
            return self._run_host_ops(program, scope, fetch_names,
                                      return_numpy)

        spec, feed_arrays = _feed_spec(block, feed)

        import os as _os

        acp_on = _os.environ.get("PADDLE_RUNNING_ENV") == \
            "PADDLE_EDL_AUTO_CHECKPOINT" or _acp_configured()
        if acp_on:
            from ..incubate.checkpoint import auto_checkpoint as _acp

            _acp.maybe_resume(self, program, scope, fed=bool(feed))

        fetches, inflight = self._dispatch(program, feed, feed_arrays, spec,
                                           fetch_names, scope,
                                           multi_step=False,
                                           scan_steps=None,
                                           use_prune=use_prune)

        # localsgd strategy: periodic cross-replica parameter averaging
        # (set by LocalSGDMetaOptimizer; see fleet/collective_transpiler.py)
        localsgd = getattr(program, "_localsgd", None)
        if localsgd is not None:
            localsgd.average_step(self, scope=scope)

        # auto-checkpoint hook (reference executor.py:1200)
        if acp_on:
            from ..incubate.checkpoint import auto_checkpoint as _acp

            _acp.on_executor_run(self, program, scope, fed=bool(feed))

        if inflight is not None:
            # pipelined mode (FLAGS_max_inflight_steps > 0): a lazy
            # handle — the device->host sync happens when the caller
            # reads an item (or at a window-drain point), never here
            return StepHandle(fetches, window=self._window, entry=inflight,
                              materialize=return_numpy)
        if return_numpy:
            from ..observe import tracer as otrace

            # legacy sync mode: the host-blocking device->host transfer
            # of the fetch list (reference Executor fetch phase)
            with otrace.span("executor/fetch", n=len(fetches)):
                return [np.asarray(v) for v in fetches]
        return list(fetches)

    # ------------------------------------------------------------------
    def warmup(
        self,
        program: Optional[Program] = None,
        feed_specs: Optional[Sequence[Dict]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
    ) -> int:
        """AOT-compile one executable per feed spec (the serving layer's
        warm start; reference AnalysisPredictor warms by running once —
        here every shape bucket is warmed BEFORE traffic arrives).

        ``feed_specs`` is an iterable of feed descriptions: each one a
        dict mapping feed name -> ``(shape, dtype)`` (or a concrete
        array used as-is).  Every spec is run once on zero-filled feeds
        through the normal compile-cache path, so later ``run`` calls
        with the same shapes are pure cache hits.  All scope variables
        the warmup runs wrote — including the RNG key — are restored
        afterwards: warmup is state-neutral.  Returns the number of
        executables freshly compiled (0 if every spec was already
        cached).
        """
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        if fetch_list is None:
            names = getattr(program, "_fetch_names", None)
            if not names:
                raise ValueError(
                    "warmup needs fetch_list= (or a program that records "
                    "its fetch contract, e.g. via load_inference_model)")
            fetch_list = [program.global_block.var(n) for n in names]
        n0 = len(self._cache)
        # device arrays must be COPIED, not just re-referenced: the jitted
        # step donates the state tuple (donate_argnums), so the warmup run
        # deletes the live buffers and a shallow snapshot would restore
        # dead arrays.  The whole scope CHAIN is snapshotted — state read
        # through a parent scope is donated all the same.
        snapshots = []
        s = scope
        while s is not None:
            snapshots.append((s, {
                k: (v.copy() if _is_jax_array(v) else v)
                for k, v in s._vars.items()
            }))
            s = s._parent
        try:
            for spec in (feed_specs or []):
                feed = {}
                for name, sd in spec.items():
                    if isinstance(sd, np.ndarray) or _is_jax_array(sd):
                        feed[name] = sd
                    else:
                        shape, dtype = sd
                        feed[name] = np.zeros(
                            tuple(int(s) for s in shape), dtype)
                self.run(program, feed=feed, fetch_list=fetch_list,
                         scope=scope)
        finally:
            # quiesce before restoring: warmup steps still in the
            # pipelined window must finish (and account their telemetry)
            # before their scope writes are rolled back.  The restore
            # must run even when the drain RAISES (a warmup step failing
            # on device): skipping it would leave warmup-mutated —
            # donation-dead — state in the user's scope
            try:
                self.drain()
            finally:
                for s, snap in snapshots:
                    s._vars.clear()
                    s._vars.update(snap)
        return len(self._cache) - n0

    # ------------------------------------------------------------------
    def run_steps(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, np.ndarray]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = False,
        steps: Optional[int] = None,
    ):
        """Run the program K times in ONE XLA executable call.

        Two feed modes:
        - ``steps=None``: every feed carries a leading step dimension of
          equal extent K (one batch per step).
        - ``steps=K``: feeds are single-step shaped and the SAME batch is
          reused for all K steps without re-transfer (synthetic-data /
          warm-cache benchmarking mode).

        The whole block is wrapped in ``lax.scan`` over the step dim, so
        the K steps run back-to-back on device with zero host round-trips —
        the TPU-native replacement for the reference's
        ``train_from_dataset`` C++ loop (executor.cc:166) + buffered_reader
        double-buffering.  Fetches come back stacked with a leading K dim,
        as device arrays by default (jax arrays are async: no sync until
        the caller converts/reads them).
        """
        import jax

        program = program if program is not None else default_main_program()
        feed = dict(feed or {})
        if not feed:
            raise ValueError("run_steps requires at least one feed")
        scope = scope if scope is not None else global_scope()
        fetch_names = tuple(
            v.name if isinstance(v, Variable) else str(v) for v in (fetch_list or [])
        )
        if getattr(program, "_localsgd", None) is not None:
            raise NotImplementedError(
                "run_steps does not support localsgd programs: the periodic "
                "parameter averaging hook runs between executor calls and "
                "would be skipped inside the on-device scan; use exe.run")
        block = program.global_block
        if steps is None:
            step_dims = {np.shape(v)[0] for v in feed.values()}
            if len(step_dims) != 1:
                raise ValueError(
                    f"all run_steps feeds must share the same leading step "
                    f"dim; got {sorted(step_dims)}")
            if 0 in step_dims:
                raise ValueError("run_steps needs at least one step")
            # spec over the per-step shapes (leading dim stripped); device
            # arrays are sliced lazily — no host transfer
            per_step_feed = {
                k: (v[0] if _is_jax_array(v) else np.asarray(v)[0])
                for k, v in feed.items()
            }
            spec, _ = _feed_spec(block, per_step_feed)
        else:
            if steps < 1:
                raise ValueError(f"steps must be >= 1, got {steps}")
            spec, _ = _feed_spec(block, feed)
        feed_arrays = {}
        for name, _, dt in spec:
            arr = feed[name]
            if _is_jax_array(arr):  # device arrays pass through untouched
                feed_arrays[name] = arr
                continue
            arr = np.asarray(arr)
            if str(arr.dtype) != dt:
                arr = arr.astype(dt)
            feed_arrays[name] = arr

        fetches, inflight = self._dispatch(program, feed, feed_arrays, spec,
                                           fetch_names, scope,
                                           multi_step=True,
                                           scan_steps=steps)
        if inflight is not None:
            return StepHandle(fetches, window=self._window, entry=inflight,
                              materialize=return_numpy)
        if return_numpy:
            return [np.asarray(v) for v in fetches]
        return list(fetches)

    # ------------------------------------------------------------------
    def run_persistent(
        self,
        fn,
        state_names: Sequence[str],
        args: Sequence = (),
        scope: Optional[Scope] = None,
    ):
        """Run one step of a pre-jitted function whose PERSISTENT state
        lives in ``scope`` as device arrays — the ``run_steps``-style
        entry for externally-built steps (the serving decode engine's
        KV cache rides this: the cache tensors never round-trip to
        host between steps).

        ``fn(state_tuple, *args) -> (outputs, new_state_tuple)`` where
        ``state_tuple`` is the current device value of every name in
        ``state_names`` (in order).  The caller owns jitting — jit with
        ``donate_argnums=(0,)`` so each step updates the state buffers
        in place on TPU/GPU.  After the call the scope holds the new
        state, so checkpoint/inspection paths (``np.asarray`` on the
        var) keep working, and the executor's dispatch/drain counters
        move so the stall watchdog and health plane see decode progress
        like any other step.
        """
        from ..monitor import stat_add
        from ..observe import tracer as otrace

        scope = scope if scope is not None else global_scope()
        missing = [n for n in state_names if not scope.has_var(n)]
        if missing:
            raise KeyError(
                f"run_persistent state vars not in scope: {missing}")
        state = tuple(scope.get_var(n) for n in state_names)
        with otrace.span("executor/persistent", state=len(state)):
            outputs, new_state = fn(state, *args)
        if len(new_state) != len(state):
            raise ValueError(
                f"run_persistent fn returned {len(new_state)} state "
                f"values for {len(state)} state vars")
        for n, v in zip(state_names, new_state):
            scope.set_var(n, v)
        # persistent steps are synchronous from the window's point of
        # view (the caller reads the outputs immediately): count them
        # dispatched AND drained so progress telemetry stays truthful
        stat_add("executor_run")
        stat_add("executor_steps_dispatched")
        stat_add("executor_steps_drained")
        return outputs

    # ------------------------------------------------------------------
    def _dispatch(self, program, feed, feed_arrays, spec, fetch_names, scope,
                  multi_step, scan_steps, use_prune=False):
        """Shared run/run_steps tail: state analysis, compile-cache lookup,
        RNG seeding, the executable call, and scope write-back.  Every
        phase is a tracer span (observe/tracer.py) and every call feeds
        the StepTimer (observe/step_stats.py) — the per-run cost of both
        is a flag check when the tracer is off.

        Returns ``(fetches, inflight)``: ``inflight`` is the window
        entry when the call was dispatched pipelined
        (FLAGS_max_inflight_steps > 0), else None (legacy sync mode)."""
        from ..observe import tracer as otrace

        with otrace.span("executor/run", multi_step=bool(multi_step)):
            return self._dispatch_impl(program, feed, feed_arrays, spec,
                                       fetch_names, scope, multi_step,
                                       scan_steps, use_prune)

    def _dispatch_impl(self, program, feed, feed_arrays, spec, fetch_names,
                       scope, multi_step, scan_steps, use_prune=False):
        import time as _time

        import jax

        from . import flags
        from ..monitor import stat_add
        from ..observe import step_stats as _step_stats
        from ..observe import tracer as otrace

        # phase attribution: dispatch-side host seconds = entry-to-launch
        # wall MINUS the backpressure drain block (that block is an older
        # step's sync time, charged to that step at ITS drain)
        t_enter = _time.perf_counter()
        t_backpressure = 0.0

        # graph-pass pipeline (framework/passes.py): fused gradient
        # allreduce + cast/dead-op cleanup, applied to a cached clone so
        # the caller's program is never mutated
        with otrace.span("executor/pass_pipeline"):
            program = self._apply_graph_passes(program, fetch_names, feed,
                                               scope)

        # scan-over-layers stacker (LayerScanPass): per-layer weight
        # families ride the compiled step as ONE stacked carrier array
        # each; the scope keeps serving per-layer names through
        # StackedParamRef views.  Runs on EVERY compile path (single-
        # device, shard_map dp, GSPMD tp, run_steps) BEFORE state
        # analysis — the analysis reads the carrier names and must find
        # them in the scope.  Steady state is a no-op per dispatch.
        lplan = getattr(program, "_layer_plan", None)
        if lplan is not None:
            lplan.ensure_stacked(scope)

        ops = None
        if use_prune and fetch_names:
            pkey = (program.fingerprint(), fetch_names)
            ops = self._prune_cache.get(pkey)
            if ops is None:
                ops = _prune_ops(program, fetch_names)
                self._prune_cache[pkey] = ops
            else:
                stat_add("executor_prune_cache_hit")
        nan_scan = bool(flags.flag("check_nan_inf"))

        # state the program will read from the scope (the full op walk is
        # cached; cache hits only re-check that the state vars still exist)
        akey = (program.fingerprint(), frozenset(feed), scope.serial,
                fetch_names if ops is not None else None)
        cached = self._analysis_cache.get(akey)
        if cached is not None and all(scope.has_var(n) for n in cached[0]):
            state_in, state_out = cached
            stat_add("executor_analysis_cache_hit")
        else:
            with otrace.span("executor/analysis"):
                state_in, state_out = self._analyze_state(
                    program, set(feed), scope, ops=ops)
            self._analysis_cache[akey] = (state_in, state_out)
        def _svspec(n):
            v = scope.get_var(n)
            if isinstance(v, (PackedParamRef, StackedParamRef)) \
                    or _is_jax_array(v):
                return (n, tuple(v.shape), str(v.dtype))
            return (n, tuple(np.shape(v)), str(np.asarray(v).dtype))

        state_spec = tuple(_svspec(n) for n in state_in)

        mesh = self._active_mesh()
        key = (
            ("multi_step", scan_steps) if multi_step else None,
            program.fingerprint(),
            spec,
            fetch_names,
            state_spec,
            type(self.place).__name__,
            self.place.device_id,
            id(mesh),
            ops is not None,
            nan_scan,
            # flags read at trace time must key the cache, or flipping
            # them between runs is silently ignored; any flag defined
            # with affects_lowering=True joins automatically
            flags.lowering_key(),
        )
        from ..observe import flight as _flight

        entry = self._cache.get(key)
        if entry is None:
            stat_add("executor_compile")
            # the backend is definitionally in use from here on: the
            # one safe point to flight-record the device topology
            # (jax.devices() on a DEAD backend is the hang itself) —
            # and to unlock the heartbeat's live HBM sampling for the
            # same reason (observe/xla_stats.py)
            _flight.record_device_topology()
            from ..observe import xla_stats as _xla_stats

            _xla_stats.mark_backend_in_use()
            _flight.record("executor/compile",
                           fingerprint=program.fingerprint()[:16],
                           fetches=len(fetch_names),
                           multi_step=bool(multi_step))
            entry = self._compile(program, spec, state_in, state_out,
                                  fetch_names, mesh=mesh,
                                  multi_step=multi_step, scan_steps=scan_steps,
                                  ops=ops, nan_scan=nan_scan)
            self._cache[key] = entry
        else:
            stat_add("executor_cache_hit")
        stat_add("executor_run")

        # rng key lives in the scope so runs are deterministic/resumable
        if not scope.has_var(RNG_VAR) or scope.get_var(RNG_VAR) is None:
            seed = program.random_seed or 0
            scope.set_var(RNG_VAR, jax.random.PRNGKey(seed))

        if entry.pipeline_pack is not None:
            entry.pipeline_pack.ensure_packed(scope, mesh)

        def _state_value(n):
            # a per-layer member an unrolled edge op still reads
            # individually (a trimmed layer-scan run) lives as a
            # StackedParamRef view: hand jit the live device SLICE of
            # its carrier, not the view object
            v = scope.get_var(n)
            if isinstance(v, StackedParamRef):
                return v.device_value()
            return v

        feed_vals = tuple(feed_arrays[n] for n in entry.feed_names)
        mut_vals = tuple(_state_value(n) for n in entry.state_mut)
        const_vals = tuple(_state_value(n) for n in entry.state_const)
        rng = scope.get_var(RNG_VAR)

        if entry.globalize is not None:
            feed_vals, mut_vals, const_vals, rng = entry.globalize(
                feed_vals, mut_vals, const_vals, rng)

        # pipelined dispatch (FLAGS_max_inflight_steps): backpressure
        # BEFORE launching the next step so at most `max_inflight` steps
        # are ever in flight; 0 keeps the legacy synchronous-fetch path
        max_inflight = int(flags.flag("max_inflight_steps"))
        pipelined = max_inflight > 0

        if pipelined:
            _t_bp0 = _time.perf_counter()
            self._window.backpressure(max_inflight)
            t_backpressure = _time.perf_counter() - _t_bp0

        # examples/steps for the StepTimer; FLOPs/allreduce bytes are
        # the compile-time static accounting on the entry
        if multi_step:
            n_steps = scan_steps
            if n_steps is None and feed_arrays:
                n_steps = int(np.shape(next(iter(feed_arrays.values())))[0])
            n_steps = int(n_steps or 1)
        else:
            n_steps = 1
        batch = next((s[0] for _, s, _ in spec if s), 0)

        # jit traces lazily: the FIRST call of a fresh entry is the real
        # trace+XLA-compile (the "executor/lowering" span and per-
        # collective spans nest inside it); later calls are pure execute
        first_call = entry.n_calls == 0
        outer = otrace.span("executor/compile") if first_call \
            else otrace.NULL_SPAN
        t_exec0 = _time.perf_counter()
        if first_call:
            _ACTIVE_COMPILES[threading.get_ident()] = t_exec0
        try:
            with outer:
                if first_call:
                    # XLA introspection (observe/xla_stats.py): AOT
                    # lower+compile with telemetry, HBM accounting, and
                    # the pre-dispatch budget gate — MemoryBudgetError
                    # propagates from here with NOTHING dispatched
                    self._introspect_first_compile(
                        entry, program, mesh,
                        (feed_vals, mut_vals, const_vals, rng),
                        scope, spec, n_steps)
                with otrace.span("executor/execute"):
                    fetches, new_state, new_rng = entry.fn(
                        feed_vals, mut_vals, const_vals, rng)
                    if not pipelined and flags.flag("benchmark"):
                        # reference FLAGS_benchmark: sync so the recorded
                        # time is the step, not the async dispatch
                        jax.block_until_ready((fetches, new_state))
        finally:
            if first_call:
                _ACTIVE_COMPILES.pop(threading.get_ident(), None)
        entry.n_calls += 1

        for n, v in zip(entry.state_out, new_state):
            scope.set_var(n, v)
        if entry.uses_rng:
            scope.set_var(RNG_VAR, new_rng)

        if pipelined:
            nan_flags = None
            if entry.nan_scan:
                # keep the sentinel on device: the host check moves to
                # the window-drain point (no per-step sync)
                nan_flags = fetches[-1]
                fetches = fetches[:-1]
            # a fetched var that is ALSO a state output may share its
            # XLA buffer with the scope array the NEXT dispatch donates
            # (jit dedupes identical outputs); give the handle its own
            # buffer so a held, undrained fetch can't be overwritten —
            # CPU donation is a no-op, but TPU/GPU donation is real
            out_set = set(entry.state_out)
            if any(n in out_set for n in entry.fetch_names):
                import jax.numpy as jnp

                fetches = tuple(
                    jnp.copy(v) if n in out_set and _is_jax_array(v)
                    else v
                    for n, v in zip(entry.fetch_names, fetches))
            inflight = _InflightStep(
                sync_refs=tuple(fetches), nan_flags=nan_flags,
                nan_ops=entry.nan_ops, t_dispatch=t_exec0, steps=n_steps,
                examples=int(batch) * n_steps, compiled=first_call,
                flops_per_step=entry.flops_per_step,
                allreduce_bytes=entry.allreduce_bytes,
                host_s=max(t_exec0 - t_enter - t_backpressure, 0.0),
                phase_plan=entry.phase_plan)
            self._window.push(inflight)
            stat_add("executor_steps_dispatched", n_steps)
            _flight.record("executor/dispatch", steps=n_steps,
                           compiled=first_call, inflight=len(self._window))
            if flags.flag("benchmark") or entry.nan_scan:
                # both flags mean "per-call semantics": FLAGS_benchmark
                # wants the recorded time to be the step, nan-scan wants
                # the raise inside the offending run — drain right away
                # (accounting/raise still happen AT the drain point)
                self._window.drain_through(inflight)
            return fetches, inflight

        # legacy sync mode: telemetry + nan check at dispatch.  The
        # call above already blocked (or will on first read), so the
        # step counts as dispatched AND drained for the health plane
        stat_add("executor_steps_dispatched", n_steps)
        stat_add("executor_steps_drained", n_steps)
        _flight.record("executor/dispatch", steps=n_steps,
                       compiled=first_call, sync=True)
        _step_stats.step_timer().record_run(
            _time.perf_counter() - t_exec0, steps=n_steps,
            examples=int(batch) * n_steps, compiled=first_call,
            flops_per_step=entry.flops_per_step,
            allreduce_bytes_per_step=entry.allreduce_bytes)
        if entry.nan_scan:
            # NOT named `flags`: that would shadow the framework.flags
            # module imported at the top of this scope
            nan_flags = np.asarray(fetches[-1])
            fetches = fetches[:-1]
            _raise_on_nan(nan_flags, entry.nan_ops)
        return fetches, None

    # ------------------------------------------------------------------
    def _introspect_first_compile(self, entry, program, mesh, args, scope,
                                  spec, n_steps):
        """AOT-lower + compile the fresh entry BEFORE its first dispatch
        (observe/xla_stats.py): compile wall time into the
        ``compile_seconds`` histogram, executable size / HLO module
        stats / per-chip HBM footprint (``compiled.memory_analysis``)
        onto ``/metrics``, a ``compile_done`` flight event, the
        TPShardingPlan-joined per-var attribution table, and the
        ``FLAGS_hbm_budget_fraction`` gate — which raises
        :class:`~..observe.xla_stats.MemoryBudgetError` with nothing
        dispatched.  On success the compiled executable replaces the
        entry's callable so the compile is paid once.

        Everything short of a budget rejection is best-effort: a jax
        without AOT stages (or a path ``lower()`` cannot handle) falls
        back to the lazy first-call trace with the telemetry skipped."""
        from . import flags

        if entry.jit_fn is None or not flags.flag("xla_introspect"):
            return
        import contextlib
        import time as _time

        import jax

        from ..monitor import stat_add
        from ..observe import tracer as otrace
        from ..observe import xla_stats

        t0 = _time.perf_counter()
        try:
            ctx = jax.default_device(entry.jit_device) \
                if entry.jit_device is not None else contextlib.nullcontext()
            with otrace.span("executor/aot_compile"), ctx:
                compiled = entry.jit_fn.lower(*args).compile()
        except Exception as e:  # noqa: BLE001 — lazy path unchanged
            stat_add("xla_introspect_unavailable")
            logger.debug("XLA AOT introspection unavailable: %s", e)
            return
        seconds = _time.perf_counter() - t0

        # per-var sizes for the attribution join: scope state (params,
        # optimizer slots — the shardable bytes) + this call's feeds
        size_entries = []
        for name in entry.state_mut + entry.state_const:
            v = scope.get_var(name)
            if hasattr(v, "shape") and hasattr(v, "dtype"):
                size_entries.append(
                    (name, tuple(int(s) for s in v.shape), str(v.dtype),
                     "state"))
        for name, shape, dt in spec:
            size_entries.append((name, tuple(shape), dt, "feed"))
        device = entry.jit_device
        if device is None and mesh is not None:
            device = mesh.devices.flat[0]

        rec = xla_stats.on_compile(
            compiled, fingerprint=program.fingerprint(), seconds=seconds,
            size_entries=size_entries,
            plan=getattr(program, "_tp_plan", None), mesh=mesh,
            n_steps=n_steps, program_flops=entry.flops_per_step,
            device=device)
        if rec.get("xla_flops_per_step"):
            # MFU honesty: the hand-rolled IR count misprices fused ops
            # (mfu_flops_mismatch counted in on_compile) — XLA's own
            # per-chip number feeds the StepTimer from here on, and the
            # phase cost model re-prices its compute side to match
            entry.flops_per_step = float(rec["xla_flops_per_step"])
            if entry.phase_plan is not None:
                entry.phase_plan.update_flops(entry.flops_per_step)

        orig_fn = entry.fn

        def run_compiled(feed_vals, mut_vals, const_vals, rng):
            try:
                return compiled(feed_vals, mut_vals, const_vals, rng)
            except (TypeError, ValueError):
                # an input aval/sharding drifted from the AOT signature
                # (e.g. state restored from a checkpoint with another
                # layout): the lazy jit path re-specializes, an AOT
                # executable cannot — fall back permanently
                stat_add("xla_aot_fallbacks")
                entry.fn = orig_fn
                return orig_fn(feed_vals, mut_vals, const_vals, rng)

        entry.fn = run_compiled

    # ------------------------------------------------------------------
    def _apply_graph_passes(self, program, fetch_names, feed, scope):
        """Run the framework.passes pipeline over ``program`` before
        lowering (reference build-strategy graph passes).  The result —
        a rewritten clone, or the original object when no pass changed
        anything — is cached per (fingerprint, pass config, fetch/feed
        names, scope serial); FLAGS_fuse_passes (affects_lowering=True)
        gates the whole pipeline AND re-keys the compile cache."""
        from . import flags
        from . import passes as passes_mod

        pipe_meta = getattr(program, "_pipeline", None)
        if pipe_meta is not None:
            # the pipeline executor owns its schedule rewrite, but the
            # dp×mp×pp composition still needs ShardingPropagationPass:
            # its plan + partial anchors drive the manual Megatron mp
            # sharding inside the GPipe shard_map
            # (distributed/pipeline.py).  The fuse/cast/DCE passes stay
            # off — the pipeline splits the op stream per stage itself.
            if not (passes_mod.has_tp_marks(program)
                    or passes_mod.has_ep_marks(program)):
                return program
            pipeline = passes_mod.PassPipeline(
                [passes_mod.ShardingPropagationPass()])
        elif not flags.flag("fuse_passes"):
            # FLAGS_fuse_passes gates the OPTIMIZATION passes only.  Two
            # passes answer to their own switches and still run: a
            # tensor-parallel program needs its sharding plan (the dp
            # loss-grad scale was removed at transpile time, so running
            # it un-sharded would be numerically wrong, not just slow),
            # and scan-over-layers was asked for explicitly via
            # FLAGS_layer_scan / recompute_configs scan stamps — its
            # own gate, not the fusion flag, decides it
            reduced = []
            if passes_mod.has_tp_marks(program) \
                    or passes_mod.has_ep_marks(program):
                reduced.append(passes_mod.ShardingPropagationPass())
            if passes_mod.LayerScanPass._config(program)[0]:
                reduced.append(passes_mod.LayerScanPass())
            if not reduced:
                return program
            pipeline = passes_mod.PassPipeline(reduced)
        else:
            pipeline = passes_mod.default_pipeline()
        from ..monitor import stat_add

        mesh = self._active_mesh()
        # flags read at PASS time (FLAGS_layer_scan and friends decide
        # whether/how programs are rewritten) must key the pass cache
        # exactly like they key the compile cache — flipping the scan
        # flag or the remat policy between runs must re-run the
        # pipeline, not serve the stale rewrite
        key = (program.fingerprint(), pipeline.config_key(), fetch_names,
               frozenset(feed), scope.serial, id(mesh),
               flags.lowering_key())
        cached = self._pass_cache.get(key)
        if cached is not None:
            stat_add("executor_pass_cache_hit")
            return cached
        ctx = passes_mod.PassContext(fetch_names=fetch_names,
                                     feed_names=tuple(feed), scope=scope,
                                     mesh=mesh)
        out = pipeline.apply(program, ctx)
        if out is not program and pipe_meta is not None:
            # clone() is a proto round-trip: the pipeline metadata is a
            # python attr and must ride onto the rewritten clone or the
            # compile path would fall through to the non-pipeline branch
            out._pipeline = pipe_meta
        self._pass_cache[key] = out
        return out

    # ------------------------------------------------------------------
    def _run_host_ops(self, program, scope, fetch_names, return_numpy):
        """Interpret a host I/O block (save/load programs).  Mixed
        compute+io blocks are rejected: build a separate save program as
        the reference's io.py does."""
        # a save program must observe a quiescent pipeline (telemetry +
        # NaN checks of in-flight steps fire before any file is written)
        self.drain()
        from . import var_io

        block = program.global_block
        for op in block.ops:
            if op.type in PSEUDO_OPS:
                continue
            if op.type not in HOST_OPS:
                raise NotImplementedError(
                    f"op {op.type!r} cannot run in a host I/O program; "
                    f"save/load programs must contain only save/load ops "
                    f"(build them via fluid.io helpers)")
            if op.type == "save":
                name = op.inputs["X"][0]
                var_io.save_var(np.asarray(scope.get_var(name)),
                                op.attr("file_path"))
            elif op.type == "load":
                name = op.outputs["Out"][0]
                scope.set_var(name, var_io.load_var(op.attr("file_path")))
            elif op.type == "save_combine":
                names = list(op.inputs["X"])
                var_io.save_combine(
                    {n: np.asarray(scope.get_var(n)) for n in names},
                    names, op.attr("file_path"))
            elif op.type == "load_combine":
                names = list(op.outputs["Out"])
                loaded = var_io.load_combine(op.attr("file_path"))
                missing = [n for n in names if n not in loaded]
                if missing:
                    raise KeyError(
                        f"load_combine: vars {missing} not present in "
                        f"{op.attr('file_path')!r}")
                for n in names:
                    scope.set_var(n, loaded[n])
        if fetch_names:
            vals = [scope.get_var(n) for n in fetch_names]
            return [np.asarray(v) for v in vals] if return_numpy else vals
        return []

    # ------------------------------------------------------------------
    def _analyze_state(self, program: Program, feed_names: set, scope: Scope,
                       ops=None):
        """Static use/def analysis of the root block (plus sub-blocks).

        state_in  = names read before written that are not feeds (must come
                    from the scope: parameters, optimizer state, ...)
        state_out = names written that should persist back into the scope
                    (persistable vars, or anything already living in scope).
        ``ops`` restricts the walk to a pruned op list (use_prune).
        """
        written: set = set()
        state_in: List[str] = []
        state_out: List[str] = []
        seen_out: set = set()

        def visit_block(block, op_list):
            for op in op_list:
                if op.type in PSEUDO_OPS:
                    continue
                reads = list(op.input_arg_names()) \
                    + _ctrl_attr_reads(program, op)
                for aname in ("sub_block", "sub_block_t", "sub_block_f"):
                    if op.has_attr(aname):
                        reads.extend(
                            _sub_external_reads(program, int(op.attr(aname))))
                for name in reads:
                    if name in feed_names or name in written:
                        continue
                    if name not in state_in:
                        if not scope.has_var(name) or scope.get_var(name) is None:
                            raise RuntimeError(
                                f"op {op.type!r} reads {name!r} which is neither a "
                                f"feed nor initialized in the scope. Did you run the "
                                f"startup program? (op built at: "
                                f"{op.callstack[-1] if op.callstack else '?'})"
                            )
                        state_in.append(name)
                for name in op.output_arg_names():
                    written.add(name)
                    var = block._find_var_recursive(name)
                    persistable = (var is not None and var.persistable) or scope.has_var(name)
                    if persistable and name not in seen_out:
                        seen_out.add(name)
                        state_out.append(name)

        block = program.global_block
        visit_block(block, ops if ops is not None else block.ops)
        return tuple(state_in), tuple(state_out)

    # ------------------------------------------------------------------
    def _compile(self, program, feed_spec, state_in, state_out, fetch_names,
                 mesh=None, multi_step=False, scan_steps=None, ops=None,
                 nan_scan=False) -> _Compiled:
        import jax
        import jax.numpy as jnp

        feed_names = tuple(n for n, _, _ in feed_spec)
        block = program.global_block
        op_list = [op for op in (ops if ops is not None else block.ops)
                   if op.type not in PSEUDO_OPS]
        # tensor-parallel plan (ShardingPropagationPass output on the
        # post-pass program).  A tp-stamped program WITHOUT a plan means
        # the pass could not run — refuse rather than fall through to
        # the shard_map dp path, whose gradient math assumes the dp
        # loss-grad scale the tp transpile removed.
        tp_plan = getattr(program, "_tp_plan", None)
        if tp_plan is None:
            from .passes import has_ep_marks, has_tp_marks

            if has_tp_marks(program):
                raise ValueError(
                    "this program was built with DistributedStrategy."
                    "tensor_parallel but the executor has no mesh with "
                    "an 'mp' axis; build one with init_parallel_env("
                    "mesh_shape=(dp, mp), axis_names=('dp', 'mp')) or "
                    "set_mesh(Mesh(devs.reshape(dp, mp), ('dp', 'mp')))")
            if has_ep_marks(program):
                raise ValueError(
                    "this program was built with DistributedStrategy."
                    "expert_parallel but the executor has no mesh with "
                    "an 'ep' axis; build one with init_parallel_env("
                    "mesh_shape=(dp, ep), axis_names=('dp', 'ep')) or "
                    "FLAGS_ep_degree")
        # static per-step accounting for the StepTimer/MFU readout; a
        # failure here must never fail a compile
        try:
            from ..hapi.model_stat import program_flops

            flops_per_step = float(program_flops(program))
            # a symbolic-batch program (-1 leading dims) prices
            # per-SAMPLE FLOPs (model_stat counts -1 as 1): scale by
            # the concrete feed batch this executable was compiled for
            if feed_spec and flops_per_step:
                name0, shape0, _ = feed_spec[0]
                var0 = block._find_var_recursive(name0)
                if (var0 is not None and var0.shape and shape0
                        and int(var0.shape[0]) <= 0):
                    flops_per_step *= max(int(shape0[0]), 1)
        except Exception:  # noqa: BLE001 — telemetry only
            flops_per_step = 0.0
        if tp_plan is not None:
            # per-CHIP FLOPs under tensor parallelism: each chip holds
            # 1/mp of every sharded layer, so comparing program FLOPs
            # against FLAGS_device_peak_tflops without the division
            # overstates MFU by mp× on sharded runs
            flops_per_step /= max(tp_plan.mp_degree, 1)
            # per-grad dp-allreduce payloads from the plan: mp-sharded
            # grads move only their shard over dp (the post-pass op
            # stream's var shapes are global and would overcount)
            allreduce_bytes = sum(
                int(r.get("bytes", 0))
                for r in tp_plan.grad_reduce.values())
        else:
            allreduce_bytes = _program_allreduce_bytes(block, op_list)
        # step-phase attribution (observe/phases.py): price this
        # program's compute + collectives once at compile; consulted at
        # every window drain.  Never fails a compile (None on error).
        from . import flags as _pflags
        from ..observe import phases as _phases

        phase_plan = _phases.build_phase_plan(
            block, op_list, mesh=mesh, tp_plan=tp_plan,
            flops_per_step=flops_per_step,
            cm_chunks=int(_pflags.flag("collective_matmul_chunks") or 0)
            if tp_plan is not None else 0,
            moe_chunks=int(_pflags.flag("moe_alltoall_chunks") or 0))
        out_set = set(state_out)
        state_mut = tuple(n for n in state_in if n in out_set)
        state_const = tuple(n for n in state_in if n not in out_set)
        if nan_scan and getattr(program, "_pipeline", None) is not None:
            # the pipeline executor re-derives its own fetch contract;
            # per-op scanning inside the GPipe switch is a later
            # milestone — warn instead of breaking the run
            logger.warning("FLAGS_check_nan_inf is not supported for "
                           "pipeline programs; scan skipped")
            nan_scan = False
        if nan_scan:
            # per-op finite flags come back as an extra fetch; _dispatch
            # raises host-side naming the first bad op (reference
            # FLAGS_check_nan_inf, operator.cc:1129)
            fetch_names = tuple(fetch_names) + (NAN_FLAGS_VAR,)

        def trace_block(env, rng, axis_env=(), ring_axes=None, fold_axes=()):
            from ..observe import tracer as otrace

            ctx = LoweringContext(block, env, rng_key=rng, mesh=mesh,
                                  axis_env=axis_env, ring_axes=ring_axes,
                                  fold_axes=fold_axes)
            from . import flags as _flags_mod
            from .lowering import apply_tp_constraints
            from .passes import TP_CONSTRAINT_ATTR

            # latency-hiding collective matmul: row-chunk anchored
            # row-parallel matmuls so XLA emits one mp reduce per chunk
            # (ops/collective_matmul.py); 0/1 keeps the plain lowering
            cm_chunks = int(_flags_mod.flag("collective_matmul_chunks")
                            or 0) if tp_plan is not None else 0

            flags = []
            with otrace.span("executor/lowering", ops=len(op_list)):
                for op in op_list:
                    try:
                        chunked = False
                        if cm_chunks > 1 and mesh is not None \
                                and op.has_attr(TP_CONSTRAINT_ATTR):
                            from ..ops.collective_matmul import (
                                maybe_chunked_gspmd)

                            chunked = maybe_chunked_gspmd(
                                ctx, op, mesh, cm_chunks)
                        if chunked:
                            pass  # lowering + constraints emitted chunked
                        elif op.type in COLLECTIVE_OPS:
                            # per-collective span: payload bytes + dtype
                            # read off the traced value (host time ==
                            # trace cost; the args are what the timeline
                            # is really for)
                            with otrace.span(f"collective/{op.type}",
                                             **_collective_span_args(
                                                 env, op, mesh=mesh)):
                                get_lowering(op.type)(ctx, op)
                        else:
                            get_lowering(op.type)(ctx, op)
                        if not chunked and tp_plan is not None \
                                and op.has_attr(TP_CONSTRAINT_ATTR):
                            # sharding anchors: pin the propagated spec
                            # so XLA places the mp partial-sum reduce at
                            # THIS op (Megatron f/g operator placement)
                            apply_tp_constraints(env, op, mesh)
                    except Exception as e:
                        site = op.callstack[-1] if op.callstack \
                            else "<unknown>"
                        raise type(e)(
                            f"while lowering op {op.type!r} (built at "
                            f"{site}): {e}"
                        ) from e
                    if nan_scan:
                        ok = jnp.bool_(True)
                        for n in op.output_arg_names():
                            v = env.get(n)
                            if v is not None and hasattr(v, "dtype") \
                                    and jnp.issubdtype(v.dtype,
                                                       jnp.floating):
                                ok = jnp.logical_and(
                                    ok, jnp.isfinite(v).all())
                        flags.append(ok)
            if nan_scan:
                env[NAN_FLAGS_VAR] = jnp.stack(flags) if flags else \
                    jnp.ones((0,), jnp.bool_)
            missing = [n for n in fetch_names if n not in env]
            if missing:
                raise KeyError(f"fetch vars not produced by program: {missing}")
            return ctx

        pipe = getattr(program, "_pipeline", None)
        if pipe is not None and mesh is not None \
                and "pp" in mesh.axis_names:
            if multi_step:
                raise NotImplementedError(
                    "run_steps over the pipeline executor is not supported "
                    "yet; call run per step")
            from ..distributed.pipeline import (PACKED_STATE_VAR,
                                                build_pipeline_fn,
                                                plan_packing)

            plan = plan_packing(program, int(mesh.shape["pp"]), state_in,
                                state_out, pipe, tp_plan=tp_plan)
            owned = plan.owned_names
            ro_owned = sorted(owned & set(state_const))
            if ro_owned:
                raise NotImplementedError(
                    f"stage-owned state {ro_owned} is read-only in the "
                    f"program; pipeline state sharding expects params and "
                    f"slots to be updated each step")
            p_mut = (PACKED_STATE_VAR,) + tuple(
                n for n in state_mut if n not in owned)
            p_const = tuple(n for n in state_const if n not in owned)
            p_out = (PACKED_STATE_VAR,) + tuple(
                n for n in state_out if n not in owned)

            fn = build_pipeline_fn(
                program, mesh, feed_names, p_mut, p_const,
                p_out, fetch_names, pipe["loss_name"],
                pipe["params_grads"], pipe["num_microbatches"],
                pipe["bwd_end"], plan)
            pipe_jfn = jax.jit(fn, donate_argnums=(1,))
            return _Compiled(
                fn=pipe_jfn,
                feed_names=feed_names,
                state_mut=p_mut,
                state_const=p_const,
                state_out=p_out,
                fetch_names=fetch_names,
                uses_rng=True,
                pipeline_pack=plan,
                flops_per_step=flops_per_step,
                allreduce_bytes=allreduce_bytes,
                jit_fn=pipe_jfn,
                phase_plan=phase_plan,
            )

        globalize = None
        if tp_plan is not None:
            # tensor-parallel GSPMD path: the whole block is ONE logical
            # program jitted with NamedSharding in/out specs from the
            # plan — semantics stay single-program (loss parity is by
            # construction), sharding is pure layout, and XLA inserts
            # the dp grad reduces and mp partial-sum reduces.  The
            # placer rides the globalize hook: state laid out
            # differently (startup output, restored checkpoint) is
            # device_put onto the plan's shardings before the call.
            run_on_device, globalize = self._build_gspmd_fn(
                mesh, tp_plan, feed_spec, feed_names, state_mut,
                state_const, state_out, fetch_names, trace_block,
                multi_step=multi_step, scan_steps=scan_steps)
        elif mesh is None and not multi_step:
            def fn(feed_vals, mut_vals, const_vals, rng):
                env = {}
                env.update(zip(state_mut, mut_vals))
                env.update(zip(state_const, const_vals))
                env.update(zip(feed_names, feed_vals))
                ctx = trace_block(env, rng)
                fetches = tuple(env[n] for n in fetch_names)
                new_state = tuple(env[n] for n in state_out)
                return fetches, new_state, ctx.rng_key
        elif mesh is None and multi_step:
            def step_fn(env, key):
                ctx = trace_block(env, key)
                return tuple(env[n] for n in fetch_names), ctx.rng_key

            fn = _make_scan_fn(step_fn, state_mut, state_const, state_out,
                               feed_names, scan_steps)
        else:
            fn, globalize = self._build_sharded_fn(
                program, mesh, feed_spec, feed_names, state_mut, state_const,
                state_out, fetch_names, trace_block, multi_step=multi_step,
                scan_steps=scan_steps)

        jit_device = None
        if tp_plan is None:
            # jit traces lazily on first call; donating the mutable
            # state gives in-place parameter-update memory behavior
            # (buffers alias outputs).
            jfn = jax.jit(fn, donate_argnums=(1,))
            device = self.place.jax_device()

            if mesh is None:
                jit_device = device

                def run_on_device(feed_vals, mut_vals, const_vals, rng):
                    with jax.default_device(device):
                        return jfn(feed_vals, mut_vals, const_vals, rng)
            else:
                run_on_device = jfn  # placement is the mesh's job
        else:
            jfn = run_on_device  # _build_gspmd_fn returned the jit callable

        compiled = _Compiled(
            fn=run_on_device,
            feed_names=feed_names,
            state_mut=state_mut,
            state_const=state_const,
            state_out=tuple(state_out),
            fetch_names=fetch_names,
            uses_rng=True,
            globalize=globalize,
            nan_ops=tuple(
                (op.type, op.callstack[-1] if op.callstack else "?")
                for op in op_list) if nan_scan else (),
            nan_scan=nan_scan,
            flops_per_step=flops_per_step,
            allreduce_bytes=allreduce_bytes,
            jit_fn=jfn,
            jit_device=jit_device,
            phase_plan=phase_plan,
        )
        return compiled

    def _build_sharded_fn(self, program, mesh, feed_spec, feed_names, state_mut,
                          state_const, state_out, fetch_names, trace_block,
                          multi_step=False, scan_steps=None):
        """SPMD execution over the mesh (reference ParallelExecutor role).

        The whole block runs inside shard_map: feeds are split on their
        batch dim over the 'dp' axis, state (params/opt accumulators) is
        replicated, and the program's own c_* collective ops become real
        XLA collectives.  Fetch semantics match the reference's
        all-workers view: scalars come back as the cross-replica mean
        (== full-batch loss for mean losses), batched tensors are
        re-assembled by all_gather on dim 0.
        """
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        axis_names = tuple(mesh.axis_names)
        dp_axis = "dp" if "dp" in axis_names else axis_names[0]
        dp_size = int(mesh.shape[dp_axis])
        # feeds are process-local: each rank supplies its own shard, so
        # divisibility is judged against the devices THIS process feeds
        n_procs = len({d.process_index for d in mesh.devices.flat})
        local_dp = max(dp_size // n_procs, 1)
        try:
            from ..distributed.parallel_env import ring_axes as _ring_axes

            rings = _ring_axes()
        except ImportError:
            rings = {}

        feed_in_specs = []
        sharded_feeds = set()
        for name, shape, _ in feed_spec:
            if len(shape) == 0 or shape[0] <= 1:
                feed_in_specs.append(P())  # scalars/broadcast feeds replicate
            elif shape[0] % local_dp == 0:
                feed_in_specs.append(P(dp_axis))
                sharded_feeds.add(name)
            else:
                raise ValueError(
                    f"feed {name!r} batch dim {shape[0]} is not divisible by "
                    f"the local data-parallel degree {local_dp} (global dp "
                    f"{dp_size} over {n_procs} processes); pad the batch or "
                    f"resize the mesh (silent replication would waste "
                    f"{local_dp}x compute)")
        feed_in_specs = tuple(feed_in_specs)

        # static dp-variance analysis: which vars differ across dp shards?
        # feeds sharded on dp are varying; ops propagate variance from
        # inputs to outputs; allreduce/broadcast/allgather make values
        # replica-invariant again.  Drives the fetch re-assembly below.
        _CLEARING = {"c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
                     "c_allreduce_prod", "c_broadcast", "c_allgather",
                     "allreduce"}
        # ZeRO-1 sharded optimizer state lives split over the dp axis;
        # recorded as __sharded_accumulators__ attrs on the rewired
        # optimizer ops so it survives clone/proto round-trips
        sharded_state = set()
        for op in program.global_block.ops:
            accs = op.attr("__sharded_accumulators__", None)
            if accs:
                sharded_state.update(accs)
        varying = set(sharded_feeds) | sharded_state
        for op in program.global_block.ops:
            if op.type in PSEUDO_OPS:
                continue
            if op.type in _CLEARING:
                for n in op.output_arg_names():
                    varying.discard(n)
                continue
            if op.type == "c_shard_slice":
                varying.update(op.output_arg_names())
                continue
            if op.type == "uncoalesce_tensor":
                # split-back of a fused (already allreduced) gradient
                # buffer: the outputs inherit the BUFFER's variance, even
                # though the grad names were varying before fusion
                if any(n in varying for n in op.input_arg_names()):
                    varying.update(op.output_arg_names())
                else:
                    for n in op.output_arg_names():
                        varying.discard(n)
                continue
            if any(n in varying for n in op.input_arg_names()):
                varying.update(op.output_arg_names())

        def step_once(env, rng):
            # the program key advances identically on every shard;
            # per-shard randomness (dropout) folds the dp index in at the
            # op (LoweringContext.next_key(per_shard=True)) — replica-
            # invariant randomness (param init) must NOT differ per shard
            ctx = trace_block(env, rng, axis_env=axis_names,
                              ring_axes=rings, fold_axes=(dp_axis,))
            new_rng = ctx.rng_key if ctx.rng_consumed else rng
            fetches = []
            for n in fetch_names:
                v = env[n]
                if n == NAN_FLAGS_VAR:
                    # AND across shards (pmin of the 0/1 flags)
                    import jax.numpy as jnp

                    fetches.append(
                        lax.pmin(v.astype(jnp.int32), axis_names))
                    continue
                if n not in varying:
                    fetches.append(v)  # replica-invariant: local copy is it
                elif getattr(v, "ndim", 0) == 0 or v.size == 1:
                    # dp-varying scalars (losses, metrics): cross-replica
                    # mean == the full-batch value for mean-reduced losses
                    fetches.append(lax.pmean(v, axis_names))
                else:
                    # dp-varying batched values: re-assemble the full batch
                    fetches.append(lax.all_gather(v, dp_axis, axis=0, tiled=True))
            return tuple(fetches), new_rng

        if not multi_step:
            def traced(feed_vals, mut_vals, const_vals, rng):
                env = {}
                env.update(zip(state_mut, mut_vals))
                env.update(zip(state_const, const_vals))
                env.update(zip(feed_names, feed_vals))
                fetches, new_rng = step_once(env, rng)
                new_state = tuple(env[n] for n in state_out)
                return fetches, new_state, new_rng

            feed_specs_final = feed_in_specs
        else:
            traced = _make_scan_fn(step_once, state_mut, state_const,
                                   state_out, feed_names, scan_steps)

            if scan_steps is not None:
                # single-step-shaped feeds reused every iteration: the
                # batch dim is dim 0, same sharding as the per-step path
                feed_specs_final = feed_in_specs
            else:
                # feeds carry a leading step dim: replicate it, shard the
                # per-step batch dim (now dim 1) over dp
                feed_specs_final = tuple(
                    P(*((None,) + tuple(s))) if s else P()
                    for s in (tuple(spec) for spec in feed_in_specs)
                )

        def state_spec(n):
            return P(dp_axis) if n in sharded_state else P()

        fn = shard_map(
            traced,
            mesh=mesh,
            in_specs=(feed_specs_final,
                      tuple(state_spec(n) for n in state_mut),
                      tuple(state_spec(n) for n in state_const),
                      P()),
            out_specs=(tuple(P() for _ in fetch_names),
                       tuple(state_spec(n) for n in state_out),
                       P()),
            check_vma=False,
        )

        # ---- multi-process: each rank holds only ITS shard of the data
        # (reference trainers each feed their own batch).  jit over a
        # multi-host mesh needs global jax.Arrays, so process-local
        # feeds/state are assembled with make_array_from_process_local_data
        # (the jax.distributed rendezvous replaces c_gen_nccl_id /
        # c_comm_init; SURVEY §5 comm backend).
        multiproc = any(d.process_index != jax.process_index()
                        for d in mesh.devices.flat)
        globalize = None
        if multiproc:
            from jax.sharding import NamedSharding

            proc = jax.process_index()
            # contiguous process blocks along dp (mesh devices are built
            # process-major, see parallel_env.init_parallel_env); only
            # valid when processes tile the dp axis alone — a mesh whose
            # OTHER axes span processes would make the dp block span
            # several processes and the slice below wrong
            procs_on_dp = sorted({d.process_index
                                  for d in mesh.devices.flat})
            if sharded_state:
                dp_idx = axis_names.index(dp_axis)
                rows = np.moveaxis(mesh.devices, dp_idx, 0)
                if any(len({d.process_index for d in np.ravel(row)}) != 1
                       for row in rows):
                    raise NotImplementedError(
                        f"ZeRO-sharded state on a multi-process mesh "
                        f"requires each '{dp_axis}' position to belong to "
                        f"exactly one process (processes must tile the dp "
                        f"axis); reshape the mesh or disable sharding")
            proc_pos = procs_on_dp.index(proc)

            def to_global(val, pspec, state_name=None):
                if _is_jax_array(val) and not getattr(
                        val, "is_fully_addressable", True):
                    return val  # already a global array (prior step output)
                arr = np.asarray(val)
                if state_name is not None and state_name in sharded_state \
                        and arr.shape:
                    # ZeRO state: every process initialized the FULL
                    # array (replicated startup); hand jax only the
                    # slice this process's dp block owns
                    blk = arr.shape[0] // len(procs_on_dp)
                    arr = arr[proc_pos * blk:(proc_pos + 1) * blk]
                return jax.make_array_from_process_local_data(
                    NamedSharding(mesh, pspec), arr)

            def globalize(feed_vals, mut_vals, const_vals, rng):
                feeds = tuple(to_global(v, s)
                              for v, s in zip(feed_vals, feed_specs_final))
                muts = tuple(
                    to_global(v, state_spec(n), state_name=n)
                    for n, v in zip(state_mut, mut_vals))
                consts = tuple(
                    to_global(v, state_spec(n), state_name=n)
                    for n, v in zip(state_const, const_vals))
                return feeds, muts, consts, to_global(rng, P())

        return fn, globalize

    def _build_gspmd_fn(self, mesh, tp_plan, feed_spec, feed_names,
                        state_mut, state_const, state_out, fetch_names,
                        trace_block, multi_step=False, scan_steps=None):
        """Tensor-parallel execution: ``jax.jit`` over the dp×mp mesh
        with per-var ``NamedSharding`` in/out specs from the
        :class:`~.passes.TPShardingPlan` (GSPMD; SNIPPETS.md [2]/[3]
        pjit substrate).

        Unlike the shard_map dp path there is no manual axis
        environment: the traced program keeps GLOBAL shapes and
        single-program semantics (program c_* collectives lower to
        identity), the in/out shardings lay state out over the mesh —
        tp-matched params and their optimizer slots physically live as
        1/mp shards per chip — and XLA's SPMD partitioner inserts the
        collectives: dp all-reduces for gradients (over shard-sized
        payloads, since grads inherit their param's mp sharding) and
        mp partial-sum reduces at the pass's constraint anchors.

        Scope arrays come back sharded and stay sharded across steps
        (donation aliases them in place); fetches are forced replicated
        so handle reads and ``np.asarray`` reassemble transparently."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if any(d.process_index != jax.process_index()
               for d in mesh.devices.flat):
            raise NotImplementedError(
                "tensor_parallel over a multi-process mesh is not "
                "implemented yet: process-local shards would need "
                "make_array_from_process_local_data assembly per the "
                "plan's 2D specs; run one process (all chips local) or "
                "use the dp-only shard_map path")

        dp_axis = tp_plan.dp_axis if tp_plan.dp_axis in mesh.axis_names \
            else None
        dp_size = int(mesh.shape[dp_axis]) if dp_axis else 1

        def feed_pspec(shape):
            # batch-dim dp sharding when it divides evenly; GSPMD
            # semantics are identical either way (a replicated feed
            # still computes the same global value), so non-divisible
            # batches replicate instead of erroring like the shard_map
            # path must
            if (not shape or dp_axis is None or int(shape[0]) <= 1
                    or int(shape[0]) % dp_size):
                return P()
            return P(dp_axis)

        base_feed_specs = tuple(feed_pspec(s) for _, s, _ in feed_spec)
        if multi_step and scan_steps is None:
            # stacked feeds: leading step dim replicated, per-step batch
            # dim (now dim 1) sharded over dp
            feed_specs = tuple(P(*((None,) + tuple(s)))
                               for s in base_feed_specs)
        else:
            feed_specs = base_feed_specs

        def state_sharding(n):
            return NamedSharding(mesh, tp_plan.partition_spec(n))

        repl = NamedSharding(mesh, P())

        if not multi_step:
            def traced(feed_vals, mut_vals, const_vals, rng):
                env = {}
                env.update(zip(state_mut, mut_vals))
                env.update(zip(state_const, const_vals))
                env.update(zip(feed_names, feed_vals))
                ctx = trace_block(env, rng)
                fetches = tuple(env[n] for n in fetch_names)
                new_state = tuple(env[n] for n in state_out)
                return fetches, new_state, ctx.rng_key
        else:
            def step_fn(env, key):
                ctx = trace_block(env, key)
                return tuple(env[n] for n in fetch_names), ctx.rng_key

            traced = _make_scan_fn(step_fn, state_mut, state_const,
                                   state_out, feed_names, scan_steps)

        feed_sh = tuple(NamedSharding(mesh, s) for s in feed_specs)
        mut_sh = tuple(state_sharding(n) for n in state_mut)
        const_sh = tuple(state_sharding(n) for n in state_const)
        in_sh = (feed_sh, mut_sh, const_sh, repl)
        out_sh = (tuple(repl for _ in fetch_names),
                  tuple(state_sharding(n) for n in state_out),
                  repl)
        jfn = jax.jit(traced, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=(1,))

        def _place(vals, shardings):
            # jit with explicit in_shardings REJECTS committed arrays
            # laid out differently (e.g. mesh-replicated startup output,
            # or a checkpoint restored onto another topology): reshard
            # those with device_put.  Steady-state arrays already match
            # (the step's out_shardings produced them) and np feeds are
            # sharded by jit itself — both skip the copy.
            return tuple(
                jax.device_put(v, s)
                if _is_jax_array(v) and getattr(v, "sharding", None) != s
                else v
                for v, s in zip(vals, shardings))

        def placer(feed_vals, mut_vals, const_vals, rng):
            return (_place(feed_vals, feed_sh), _place(mut_vals, mut_sh),
                    _place(const_vals, const_sh),
                    _place((rng,), (repl,))[0])

        return jfn, placer

    def drain(self):
        """Block until every in-flight pipelined step has completed:
        telemetry is recorded, NaN-scan flags are checked, and the scope
        holds a quiescent state.  No-op when nothing is in flight."""
        self._window.drain_all()

    def close(self):
        # quiesce the pipeline first: in-flight steps must complete (and
        # their telemetry/NaN checks fire) before caches are dropped
        self.drain()
        # drain pending async checkpoint saves NEXT: a shutdown must
        # never abandon a queued snapshot mid-write (the manager's
        # atomic commit makes a torn abort recoverable, but a clean
        # close should finish the work it accepted)
        try:
            from ..ckpt import wait_all as _ckpt_wait_all

            _ckpt_wait_all(raise_errors=False)
        except ImportError:  # pragma: no cover - partial installs
            pass
        # clear EVERY per-program cache: long-lived serving processes
        # otherwise leak analysis/prune/pass entries for dead programs
        self._cache.clear()
        self._analysis_cache.clear()
        self._prune_cache.clear()
        self._pass_cache.clear()


# the one shared jax-Array duck-type probe lives in scope.py (leaf
# module); this alias keeps the historical local name
_is_jax_array = _is_device_array


def _acp_configured() -> bool:
    import sys

    acp = sys.modules.get("paddle_tpu.incubate.checkpoint.auto_checkpoint")
    return acp is not None and acp._cfg is not None


# ---------------------------------------------------------------------------
# convenience used by tests and the fluid-style API
# ---------------------------------------------------------------------------


def run_startup(startup_program=None, place=None, scope=None):
    from .program import default_startup_program

    exe = Executor(place or CPUPlace())
    exe.run(startup_program or default_startup_program(), scope=scope)
    return exe
