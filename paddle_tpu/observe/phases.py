"""Step-phase attribution: where a drained step's wall time goes.

The overlap schedule (PR 15) and fused kernels (PR 17) are judged by
one headline number each (``overlap_step_time_ratio``, MFU) — neither
says *where* a step's wall time actually went.  This module decomposes
every drained step into four buckets that sum exactly to its wall time:

- **compute**      — device time spent in the program's math,
- **comm_exposed** — device time stalled on collectives NOT hidden
  under compute (the number the overlap schedule exists to shrink),
- **host**         — dispatch-side host work (pass pipeline, state
  analysis, feed conversion) measured on the dispatch path,
- **input_wait**   — everything else between drains: the data loader
  and user code between ``run`` calls.

Two sources feed the split, and both are reported:

- **Measured** (``phase_*``): the window-drain timestamps the executor
  already takes (PR 5) — ``host`` is the dispatch-side host seconds
  carried on the in-flight entry, the drain's blocking time is the
  device-bound share, and the remainder of the inter-drain wall is
  input wait.  The device-bound share is split compute : exposed-comm
  by the cost model's predicted ratio (a host cannot see inside one
  ``block_until_ready``; a ``jax.profiler`` capture — see
  ``observe/profiler_capture.py`` — is the ground-truth refinement on
  real devices).
- **Predicted** (``phase_predicted_*``): a deterministic compile-time
  cost model — FLOPs (``hapi/model_stat`` or XLA's own
  ``cost_analysis`` count) over ``FLAGS_device_peak_tflops``, plus
  per-collective byte transfer times over
  ``FLAGS_phase_interconnect_gbps``.  Collectives stamped
  ``__comm_overlap__`` by FuseAllReducePass's stretch (and every
  collective-matmul chunk reduce except the last) hide under the
  remaining compute budget; the rest are exposed.  Static inputs only,
  so CPU/tier-1 runs get the same fractions every time.

The **collective ledger** prices every collective individually, keyed
by the FuseAllReducePass bucket / collective-matmul chunk identity
(``__comm_id__`` op attr): per-key ``exposed_s`` vs ``hidden_s``, so
``overlap_step_time_ratio`` finally has a per-bucket explanation and
``/metrics/cluster`` can say *why* a rank straggles ("rank 3: 41%
exposed-allreduce").  Cumulative totals ride ``/metrics`` as
``comm_exposed_seconds_micro`` / ``comm_hidden_seconds_micro`` /
``comm_exposed_share_ppm``.

Pure observer: gated by ``FLAGS_phase_attribution`` (no lowering
effect), fed only from timestamps the drain path already takes, and
held bitwise-neutral by tests/test_phases.py (``TestPureObserver``).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

from ..framework import flags as _flags
from ..monitor import stat_add, stat_set
from . import device_peaks as _peaks

__all__ = ["PhasePlan", "PhaseEngine", "phase_engine", "build_phase_plan",
           "collective_inventory", "on_step_drained", "phases_report",
           "reset_phases"]

_MICRO = 1e6

# measured bucket names, in report order; fractions are published as
# phase_<bucket>_fraction_ppm and totals as phase_<bucket>_seconds_micro
BUCKETS = ("compute", "comm_exposed", "host", "input_wait")


# ---------------------------------------------------------------------------
# compile-time collective inventory
# ---------------------------------------------------------------------------


def collective_inventory(block, op_list, mesh=None, tp_plan=None,
                         cm_chunks: int = 0,
                         moe_chunks: int = 0) -> List[dict]:
    """Per-collective entries from the post-pass op stream, in program
    order: ``{"id", "op", "dtype", "bytes", "overlap"}``.

    Byte accounting mirrors the executor's static telemetry
    (``_program_allreduce_bytes`` / ``_collective_span_args``): a
    LayerScanPass-stacked collective moves ``__layer_stack__`` x its
    var's declared bytes, and an mp-sharded grad reduce moves only its
    shard over dp.  A collective-matmul candidate op (``cm_chunks`` >
    1, single partial-sum anchor on its own single output) contributes
    one mp-reduce entry per chunk — every chunk's reduce except the
    last overlaps the next chunk's matmul, so only the tail chunk is
    exposed (ops/collective_matmul.py's latency model).

    Pure-GSPMD programs whose dp grad reduces are implicit (no
    allreduce ops in the stream) fall back to the sharding plan's
    ``grad_reduce`` table; when explicit allreduce ops exist they ARE
    the grad payload and the plan entries are skipped (no double
    count).
    """
    import math

    import numpy as np

    from ..framework import dtypes as _dtypes
    from ..framework.passes import (COMM_ID_ATTR, COMM_OVERLAP_ATTR,
                                    LAYER_STACK_ATTR, MOE_EP_ATTR,
                                    TP_CONSTRAINT_ATTR, TP_SPEC_ATTR,
                                    decode_anchor)

    mp_degree = 1
    if mesh is not None and "mp" in getattr(mesh, "axis_names", ()):
        mp_degree = int(mesh.shape["mp"])
    ep_degree = 1
    if mesh is not None and "ep" in getattr(mesh, "axis_names", ()):
        ep_degree = int(mesh.shape["ep"])

    def _var_bytes(name):
        var = block._find_var_recursive(name)
        if var is None or not var.shape or any(int(s) <= 0
                                               for s in var.shape):
            return 0, ""
        try:
            np_dt = _dtypes.to_np(var.dtype)
            itemsize = np.dtype(np_dt).itemsize
        except (KeyError, ValueError, TypeError):
            return 0, ""
        n = 1
        for s in var.shape:
            n *= int(s)
        return n * itemsize, str(np.dtype(np_dt))

    from ..framework.executor import COLLECTIVE_OPS

    entries: List[dict] = []
    saw_allreduce = False
    for op in op_list:
        if cm_chunks > 1 and mesh is not None and mp_degree > 1 \
                and op.has_attr(TP_CONSTRAINT_ATTR):
            anchors = [decode_anchor(e)
                       for e in op.attr(TP_CONSTRAINT_ATTR, [])]
            partial = [a for a in anchors if a[2]]
            outs = op.output_arg_names()
            if len(anchors) == 1 and len(partial) == 1 and len(outs) == 1 \
                    and partial[0][0] == outs[0]:
                nbytes, dt = _var_bytes(outs[0])
                if nbytes:
                    per_chunk = nbytes // cm_chunks
                    for i in range(cm_chunks):
                        entries.append({
                            "id": f"chunk:{outs[0]}@{i}",
                            "op": "mp_psum_chunk",
                            "dtype": dt,
                            "bytes": per_chunk,
                            # chunk k's reduce overlaps chunk k+1's
                            # matmul; only the LAST chunk is exposed
                            "overlap": i < cm_chunks - 1,
                        })
                continue
        if ep_degree > 1 and op.type in ("moe_ffn", "moe_ffn_grad") \
                and op.attr(MOE_EP_ATTR):
            # expert-parallel dispatch + combine all-to-all pair over
            # the [E, capacity, D] buffer (ops/moe_ops.py).  Capacity
            # is re-derived from the DECLARED shapes (symbolic batch
            # dims price per-sample — the same convention as the IR
            # FLOP estimate); with FLAGS_moe_alltoall_chunks on, each
            # all-to-all splits into capacity chunks where every chunk
            # but the last overlaps the next chunk's expert compute.
            w1 = op.inputs.get("W1", [None])[0]
            xn = op.inputs.get("X", [None])[0]
            wvar = block._find_var_recursive(w1) if w1 else None
            xvar = block._find_var_recursive(xn) if xn else None
            if wvar is None or xvar is None or len(wvar.shape) != 3:
                continue
            e, d = int(wvar.shape[0]), int(wvar.shape[1])
            tokens = 1
            symbolic = False
            for s in xvar.shape[:-1]:
                if int(s) < 0:
                    symbolic = True
                tokens *= max(int(s), 1)
            k_top = int(op.attr("top_k", 1) or 1)
            cf = float(op.attr("capacity_factor", 1.0) or 1.0)
            cap = max(1, int(math.ceil(tokens * k_top * cf / e)))
            try:
                np_dt = _dtypes.to_np(xvar.dtype)
                itemsize = np.dtype(np_dt).itemsize
                dt = str(np.dtype(np_dt))
            except (KeyError, ValueError, TypeError):
                continue
            total = e * cap * d * itemsize
            # Symbolic batch prices per-sample (cap collapses to ~1), so
            # the runtime divisibility test is meaningless here: trust
            # the flag and let the moe_alltoall_fallback counter record
            # whether the traced capacity actually engaged chunking.
            k = moe_chunks if (moe_chunks and moe_chunks > 1
                               and (symbolic or cap % moe_chunks == 0)) \
                else 1
            base = str(op.attr(COMM_ID_ATTR, "") or "") \
                or f"moe:{op.type}"
            for leg in ("dispatch", "combine"):
                for i in range(k):
                    entries.append({
                        "id": f"{base}:a2a_{leg}@{i}",
                        "op": "ep_alltoall",
                        "dtype": dt,
                        "bytes": total // k,
                        "overlap": i < k - 1,
                    })
            continue
        if op.type not in COLLECTIVE_OPS:
            continue
        names = op.input_arg_names()
        if not names:
            continue
        nbytes, dt = _var_bytes(names[0])
        if not nbytes:
            continue
        stack = max(int(op.attr(LAYER_STACK_ATTR, 0) or 0), 1)
        nbytes *= stack
        tp_spec = str(op.attr(TP_SPEC_ATTR, "") or "")
        if tp_spec and mp_degree > 1 and "mp" in tp_spec.split(","):
            nbytes //= mp_degree
        comm_id = str(op.attr(COMM_ID_ATTR, "") or "") \
            or f"{op.type}:{names[0]}"
        entries.append({
            "id": comm_id,
            "op": op.type,
            "dtype": dt,
            "bytes": int(nbytes),
            "overlap": bool(op.attr(COMM_OVERLAP_ATTR, False)),
        })
        saw_allreduce = True
    if not saw_allreduce and tp_plan is not None \
            and getattr(tp_plan, "grad_reduce", None):
        # implicit GSPMD dp grad reduces: no ops to walk, the plan's
        # per-grad payload table is the inventory
        for name, rec in sorted(tp_plan.grad_reduce.items()):
            b = int(rec.get("bytes", 0) or 0)
            if b:
                entries.append({"id": f"grad:{name}", "op": "gspmd_reduce",
                                "dtype": "", "bytes": b, "overlap": False})
    return entries


class PhasePlan:
    """Deterministic per-step cost model for one compiled program:
    predicted compute seconds + per-collective exposed/hidden seconds.

    The overlap model is a single hide-under-compute walk in program
    order: an overlap-stamped collective hides ``min(its transfer
    time, remaining compute budget)``; everything else (and any
    overflow) is exposed.  Inputs are all static — IR FLOPs, declared
    var bytes, two flags — so tier-1 CPU runs reproduce the same
    fractions every time (the "deterministic predicted phases" half of
    the contract; real-device refinement is the profiler capture's
    job)."""

    def __init__(self, flops_per_step: float, collectives: List[dict]):
        self.flops_per_step = float(flops_per_step or 0.0)
        self.collectives = list(collectives)
        self._recost()

    def _recost(self) -> None:
        peak = (_peaks.peak_tflops() or 0.0) * 1e12
        bw = float(_flags.flag("phase_interconnect_gbps") or 0.0) * 1e9
        self.compute_s = (self.flops_per_step / peak) if peak > 0 else 0.0
        budget = self.compute_s
        self.comm_exposed_s = 0.0
        self.comm_hidden_s = 0.0
        self.ledger: List[dict] = []
        per_id: Dict[str, dict] = {}
        for c in self.collectives:
            t = (c["bytes"] / bw) if bw > 0 else 0.0
            if c.get("overlap"):
                hidden = min(t, budget)
                budget -= hidden
            else:
                hidden = 0.0
            exposed = t - hidden
            self.comm_exposed_s += exposed
            self.comm_hidden_s += hidden
            row = per_id.get(c["id"])
            if row is None:
                row = per_id[c["id"]] = {
                    "id": c["id"], "op": c["op"], "dtype": c["dtype"],
                    "bytes_per_step": 0, "exposed_s": 0.0, "hidden_s": 0.0,
                    "overlap": bool(c.get("overlap"))}
                self.ledger.append(row)
            row["bytes_per_step"] += int(c["bytes"])
            row["exposed_s"] += exposed
            row["hidden_s"] += hidden

    def update_flops(self, flops_per_step: float) -> None:
        """Re-cost with XLA's own FLOP count when
        ``_introspect_first_compile`` replaces the IR estimate (the
        same MFU-honesty correction, applied to the phase model)."""
        self.flops_per_step = float(flops_per_step or 0.0)
        self._recost()

    # -- reading ---------------------------------------------------------
    @property
    def predicted_step_s(self) -> float:
        return self.compute_s + self.comm_exposed_s

    def predicted_fractions(self) -> Dict[str, float]:
        total = self.predicted_step_s
        if total <= 0.0:
            return {"compute": 0.0, "comm_exposed": 0.0}
        return {"compute": self.compute_s / total,
                "comm_exposed": self.comm_exposed_s / total}

    def to_dict(self) -> Dict:
        return {
            "flops_per_step": self.flops_per_step,
            "compute_s": self.compute_s,
            "comm_exposed_s": self.comm_exposed_s,
            "comm_hidden_s": self.comm_hidden_s,
            "predicted_step_s": self.predicted_step_s,
            "predicted_fractions": self.predicted_fractions(),
            "ledger": [dict(r) for r in self.ledger],
        }


def build_phase_plan(block, op_list, mesh=None, tp_plan=None,
                     flops_per_step: float = 0.0,
                     cm_chunks: int = 0,
                     moe_chunks: int = 0) -> Optional["PhasePlan"]:
    """Build a :class:`PhasePlan` for one compiled program (called from
    ``Executor._compile``); None when attribution is off.  Never raises
    — a cost-model failure must not fail a compile."""
    if not _flags.flag("phase_attribution"):
        return None
    try:
        inv = collective_inventory(block, op_list, mesh=mesh,
                                   tp_plan=tp_plan, cm_chunks=cm_chunks,
                                   moe_chunks=moe_chunks)
        return PhasePlan(flops_per_step, inv)
    except Exception:  # noqa: BLE001 - telemetry only
        stat_add("phase_plan_errors")
        return None


# ---------------------------------------------------------------------------
# the engine: per-drain decomposition + cumulative ledger
# ---------------------------------------------------------------------------


class PhaseEngine:
    """Accumulates the four-bucket split + collective ledger across
    drained steps; one instance per process (the executor drain feeds
    the module singleton; tests may build their own)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._zero()

    def _zero(self):
        self.steps = 0
        self.totals = {b: 0.0 for b in BUCKETS}
        self.ledger: Dict[str, dict] = {}
        self.last_plan: Optional[PhasePlan] = None

    # -- feeding (executor window drain) ---------------------------------
    def on_step_drained(self, wall_s: float, sync_s: float, host_s: float,
                        steps: int = 1, plan: Optional[PhasePlan] = None,
                        compiled: bool = False) -> Optional[Dict[str, float]]:
        """Decompose one drained step's inter-drain wall time; returns
        the per-bucket seconds (None when skipped).  First-call steps
        (``compiled``) are skipped like the StepTimer's histogram — a
        trace+XLA-compile is not a phase profile.  The four buckets sum
        exactly to ``wall_s`` by construction."""
        if not _flags.flag("phase_attribution") or compiled:
            return None
        wall = max(float(wall_s), 0.0)
        host = min(max(float(host_s), 0.0), wall)
        rest = wall - host
        sync = min(max(float(sync_s), 0.0), rest)
        input_wait = rest - sync
        # the drain block is device-bound time; split it compute vs
        # exposed comm by the model's predicted ratio (all-compute when
        # the model has nothing to say — no collectives, no flags)
        comm_frac = 0.0
        if plan is not None and plan.predicted_step_s > 0.0:
            comm_frac = plan.comm_exposed_s / plan.predicted_step_s
        comm = sync * comm_frac
        compute = sync - comm
        split = {"compute": compute, "comm_exposed": comm, "host": host,
                 "input_wait": input_wait}
        with self._lock:
            self.steps += int(steps)
            for k, v in split.items():
                self.totals[k] += v
            if plan is not None:
                self.last_plan = plan
                n = max(int(steps), 1)
                for row in plan.ledger:
                    agg = self.ledger.get(row["id"])
                    if agg is None:
                        agg = self.ledger[row["id"]] = {
                            "id": row["id"], "op": row["op"],
                            "dtype": row["dtype"],
                            "bytes_per_step": row["bytes_per_step"],
                            "overlap": row["overlap"],
                            "calls": 0, "exposed_s": 0.0, "hidden_s": 0.0}
                    agg["calls"] += n
                    agg["exposed_s"] += row["exposed_s"] * n
                    agg["hidden_s"] += row["hidden_s"] * n
            self._publish_locked()
        stat_add("phase_steps_attributed", int(steps))
        return split

    def _publish_locked(self) -> None:
        wall = sum(self.totals.values())
        for b in BUCKETS:
            stat_set(f"phase_{b}_seconds_micro",
                     int(self.totals[b] * _MICRO))
            stat_set(f"phase_{b}_fraction_ppm",
                     int(self.totals[b] / wall * 1e6) if wall > 0 else 0)
        if self.last_plan is not None:
            pf = self.last_plan.predicted_fractions()
            stat_set("phase_predicted_compute_fraction_ppm",
                     int(pf["compute"] * 1e6))
            stat_set("phase_predicted_comm_fraction_ppm",
                     int(pf["comm_exposed"] * 1e6))
        exposed = sum(r["exposed_s"] for r in self.ledger.values())
        hidden = sum(r["hidden_s"] for r in self.ledger.values())
        stat_set("comm_exposed_seconds_micro", int(exposed * _MICRO))
        stat_set("comm_hidden_seconds_micro", int(hidden * _MICRO))
        total = exposed + hidden
        stat_set("comm_exposed_share_ppm",
                 int(exposed / total * 1e6) if total > 0 else 0)

    # -- reading ---------------------------------------------------------
    def report(self) -> Dict:
        """The ``phases.json`` document: measured totals + fractions,
        the latest plan's predicted split, and the cumulative
        per-collective ledger sorted by exposed seconds."""
        with self._lock:
            wall = sum(self.totals.values())
            out: Dict = {
                "steps": self.steps,
                "wall_s": round(wall, 6),
                "measured_s": {b: round(self.totals[b], 6)
                               for b in BUCKETS},
                "measured_fractions": {
                    b: round(self.totals[b] / wall, 6) if wall > 0 else 0.0
                    for b in BUCKETS},
                "ledger": sorted(
                    (dict(r) for r in self.ledger.values()),
                    key=lambda r: -r["exposed_s"]),
            }
            exposed = sum(r["exposed_s"] for r in self.ledger.values())
            hidden = sum(r["hidden_s"] for r in self.ledger.values())
            out["comm_exposed_s"] = round(exposed, 6)
            out["comm_hidden_s"] = round(hidden, 6)
            out["comm_exposed_share"] = round(
                exposed / (exposed + hidden), 6) \
                if (exposed + hidden) > 0 else 0.0
            if self.last_plan is not None:
                out["predicted"] = self.last_plan.to_dict()
        return out

    def comm_exposed_share(self) -> float:
        """Exposed fraction of all priced comm, 0..1 (the heartbeat
        field behind the cluster straggler *cause* column)."""
        with self._lock:
            exposed = sum(r["exposed_s"] for r in self.ledger.values())
            hidden = sum(r["hidden_s"] for r in self.ledger.values())
        total = exposed + hidden
        return exposed / total if total > 0 else 0.0

    def reset(self) -> None:
        with self._lock:
            self._zero()
            self._publish_locked()


_ENGINE = PhaseEngine()


def phase_engine() -> PhaseEngine:
    return _ENGINE


def on_step_drained(wall_s: float, sync_s: float, host_s: float,
                    steps: int = 1, plan: Optional[PhasePlan] = None,
                    compiled: bool = False) -> None:
    """Drain-path hook (framework/executor.py): never raises — the
    attribution plane must not be able to fail a training step."""
    try:
        _ENGINE.on_step_drained(wall_s, sync_s, host_s, steps=steps,
                                plan=plan, compiled=compiled)
    except Exception:  # noqa: BLE001 - observer only
        stat_add("phase_attribution_errors")


def phases_report() -> Dict:
    return _ENGINE.report()


def reset_phases() -> None:
    _ENGINE.reset()
