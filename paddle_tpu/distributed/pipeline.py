"""Pipeline parallelism: GPipe schedule over a 'pp' mesh axis.

Role parity: reference fluid.optimizer.PipelineOptimizer
(optimizer.py:3695) + PipelineTrainer/SectionWorker
(framework/pipeline_trainer.cc:24, section_worker.cc:82): the program is
split into per-device sections by `device_guard("stage:N")` annotations;
micro-batches flow stage to stage.

TPU-native redesign (SURVEY.md §2.8): no section threads or blocking
queues — the whole schedule compiles into ONE XLA program executed SPMD
over the 'pp' mesh axis.  Every rank runs the same code; `lax.switch` on
`axis_index('pp')` selects the local stage, `lax.ppermute` moves boundary
activations (forward) and their cotangents (backward) between neighbor
ranks, and each stage's backward is `jax.vjp` of its traced forward.
GPipe flush schedule: K micro-batch forwards fill the pipe, then K
backwards drain it.

v3 — per-stage state sharding (the point of PP — memory):
- parameters AND optimizer slots are packed, stage by stage, into ONE
  (n_stages, width) float32 buffer physically sharded over 'pp'
  (`PartitionSpec('pp')` on dim 0), so each rank holds only its own
  stage's ~1/S of the training state.  Inside the shard_map every rank
  sees its LOCAL (width,) row; the `lax.switch` branch for stage s
  reinterprets that row with stage s's layout — on rank r branch r is
  the one selected, so the bytes always match the layout.
- the backward takes `jax.vjp` directly w.r.t. the packed row, so
  per-stage parameter gradients come back packed in the same layout and
  never leave the owning rank (no pp psum for param grads; dp still
  psums).
- optimizer ops are partitioned per stage and run inside a second
  `lax.switch`; each rank updates only its own stage's slice in place.
  Shared optimizer ops (lr schedules, counters) run replicated.
- the scope keeps lightweight `PackedParamRef` views of every owned var
  (framework/scope.py) so save/checkpoint/inspection still read true
  values and `paddle.load` writes trigger a re-pack.
- fetches are no longer loss-only: any forward activation can be
  fetched (per-microbatch values are collected on the owning stage's
  rank, psum-broadcast, and re-assembled over micro-batches and dp).

v2 capabilities retained: dropout-safe per-(stage, microbatch) RNG,
carried batch-norm stats, multi-tensor/ragged/skip boundaries via the
packed activation carrier, dp x pp meshes.

v4 — dp×mp×pp composition + collective–compute overlap:
- tensor parallelism INSIDE each stage (Megatron-style, manual): when
  the program carries a ShardingPropagationPass plan (the
  TensorParallelMetaOptimizer now composes with pipeline), rule-matched
  params and their optimizer slots are packed as per-mp-rank SHARDS —
  the packed buffer grows an mp dimension, (n_stages, mp, width)
  sharded ``P('pp','mp')`` — and the stage trace applies the Megatron
  f/g operators at the pass's constraint anchors: a column-parallel
  matmul's input rides ``f`` (identity fwd / mp-psum bwd), a
  row-parallel (contracted, "\\tP"-flagged) matmul's partial output
  rides ``g`` (mp-psum fwd / identity bwd).  Both are explicit
  ``custom_vjp``s, so ``jax.vjp`` of the staged forward produces exact
  shard gradients with no dependence on psum-transpose conventions.
- scan-over-layers INSIDE each stage: isomorphic per-layer op runs
  within one stage's forward (and its optimizer partition) are traced
  as ONE ``lax.scan`` over stacked per-layer weights (same detection
  machinery as framework/passes.py LayerScanPass, same RNG-threading
  contract, bitwise vs the unrolled trace) — trace/compile cost per
  stage becomes ~constant in stage depth.
- latency-hiding collective matmul: with
  ``FLAGS_collective_matmul_chunks`` > 1, each row-parallel
  matmul+psum decomposes into k output-row chunks whose per-chunk mp
  reduces overlap the remaining chunk matmuls
  (ops/collective_matmul.py).

Remaining restrictions (loud errors): float32 training state; boundary
tensors must be floating point; no cross-stage optimizer reductions
(global grad clip); shared (multi-stage) parameters; mp-sharded
activations may only flow through the matmul/elementwise/activation
family (softmax/dropout/layer_norm and friends need replicated inputs
— the Megatron block shape, where the row-parallel reduce precedes
them, satisfies this by construction).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

PACKED_STATE_VAR = "@PP_PACKED_STATE@"

_TP_MATMUL_TYPES = ("mul", "matmul", "matmul_v2")

# ops that provably keep a value's mp layout (elementwise over the
# local shard); everything NOT here and not handled structurally must
# see replicated inputs under pipeline×mp — validated at plan time
_MP_PRESERVING = {"relu", "gelu", "tanh", "sigmoid", "cast", "scale",
                  "assign", "c_identity", "recompute_barrier"}


def _mp_only(spec):
    return tuple("mp" if s == "mp" else None for s in (spec or ()))


def _validate_mp_flow(block, stage_ops, tp_plan):
    """Strict mp-layout walk over the staged FORWARD ops (compile
    time).  The manual pipeline×mp trace runs each op on LOCAL shard
    values, so any op outside the understood family that consumes an
    mp-sharded value would compute a silently-wrong local result —
    refuse loudly instead.  Returns the final name -> mp-spec map (the
    fetch/boundary checks read it)."""
    from ..framework.passes import (EMB_SHARD_ATTR, TP_CONSTRAINT_ATTR,
                                    decode_anchor)

    known: Dict[str, tuple] = {
        n: _mp_only(s) for n, s in tp_plan.specs.items()
        if any(x == "mp" for x in s)}

    def has_mp(n):
        return any(x == "mp" for x in known.get(n, ()))

    for si, ops in enumerate(stage_ops):
        for op in ops:
            anchors = [decode_anchor(e)
                       for e in (op.attr(TP_CONSTRAINT_ATTR, []) or [])]
            if op.type in _TP_MATMUL_TYPES:
                outs = op.output_arg_names()
                if anchors:
                    for n, spec, partial in anchors:
                        sp = _mp_only(spec)
                        if partial or not any(x == "mp" for x in sp):
                            known.pop(n, None)  # g-psum'd -> replicated
                        else:
                            known[n] = sp
                elif any(has_mp(n) for n in op.input_arg_names()):
                    raise NotImplementedError(
                        f"pipeline×mp: un-anchored {op.type!r} in stage "
                        f"{si} reads an mp-sharded value; the sharding "
                        f"pass could not classify it — adjust the "
                        f"partition rules")
                else:
                    for n in outs:
                        known.pop(n, None)
                continue
            if op.type in ("transpose", "transpose2"):
                xs = op.inputs.get("X", [])
                outs = op.output_arg_names()
                spec = known.get(xs[0]) if len(xs) == 1 else None
                axes = [int(a) for a in (op.attr("axis", []) or [])]
                if spec is not None and len(axes) == len(spec) and outs:
                    known[outs[0]] = tuple(spec[a] for a in axes)
                    continue
                if any(has_mp(n) for n in op.input_arg_names()):
                    raise NotImplementedError(
                        f"pipeline×mp: transpose of an mp-sharded value "
                        f"with unknown axes in stage {si}")
                for n in outs:
                    known.pop(n, None)
                continue
            if op.type.startswith("elementwise_") \
                    and not op.type.endswith("_grad"):
                xs = op.inputs.get("X", [])
                ys = op.inputs.get("Y", [])
                xsp = known.get(xs[0]) if xs else None
                ysp = known.get(ys[0]) if ys else None
                if ysp is not None and any(x == "mp" for x in ysp):
                    # broadcast operand sharded (a column-parallel
                    # bias): valid only when X is sharded the same way
                    # on its trailing dim
                    if xsp is None or xsp[-1] != ysp[-1]:
                        raise NotImplementedError(
                            f"pipeline×mp: {op.type!r} in stage {si} "
                            f"broadcasts mp-sharded {ys[0]!r} into a "
                            f"differently-laid-out operand")
                for n in op.output_arg_names():
                    if xsp is not None and any(x == "mp" for x in xsp):
                        known[n] = xsp
                    else:
                        known.pop(n, None)
                continue
            if op.type in ("lookup_table", "lookup_table_v2"):
                # row-sharded table: the all-to-all engine
                # (ops/embedding_ops.py) returns a value replicated on
                # mp — but ONLY when the sharding pass stamped the op;
                # an mp-sharded table reaching an unstamped lookup
                # would gather from a local shard as if it were global
                wname = op.inputs.get("W", [None])[0]
                if wname and has_mp(wname):
                    wspec = known.get(wname, ())
                    if not int(op.attr(EMB_SHARD_ATTR, 0) or 0):
                        raise NotImplementedError(
                            f"pipeline×mp: {op.type!r} in stage {si} "
                            f"reads mp-sharded table {wname!r} but the "
                            f"sharding pass did not classify it for "
                            f"the embedding engine (row-shard it: "
                            f"P('mp', None), or drop its rule)")
                    if wspec and (wspec[0] != "mp"
                                  or any(x == "mp" for x in wspec[1:])):
                        raise NotImplementedError(
                            f"pipeline×mp: embedding table {wname!r} "
                            f"in stage {si} must be ROW-sharded "
                            f"(P('mp', None)); got {wspec}")
                bad_ids = sorted(n for n in op.inputs.get("Ids", [])
                                 if has_mp(n))
                if bad_ids:
                    raise NotImplementedError(
                        f"pipeline×mp: embedding ids {bad_ids} in "
                        f"stage {si} are mp-sharded; the engine needs "
                        f"replicated ids")
                for n in op.output_arg_names():
                    known.pop(n, None)  # engine output: replicated
                continue
            if op.type in _MP_PRESERVING:
                xs = op.inputs.get("X", [])
                spec = known.get(xs[0]) if len(xs) == 1 else None
                for n in op.output_arg_names():
                    if spec is not None:
                        known[n] = spec
                    else:
                        known.pop(n, None)
                continue
            if op.type == "flash_attention":
                # the fused op keeps the Megatron shape INTERNALLY: its
                # softmax is per-head, so heads-dim (dim 1) sharded
                # q/k/v is the one layout that flows through locally —
                # no replication needed, unlike the unfused softmax op
                qn = op.inputs.get("Q", [None])[0]
                spec = known.get(qn) if qn else None
                for other in (op.inputs.get("K", [None])[0],
                              op.inputs.get("V", [None])[0]):
                    if (known.get(other) if other else None) != spec:
                        raise NotImplementedError(
                            f"pipeline×mp: flash_attention in stage "
                            f"{si} has q/k/v with mismatched mp "
                            f"layouts; shard all three on the heads "
                            f"dim or none")
                mn = op.inputs.get("Mask", [None])[0]
                if mn and has_mp(mn):
                    raise NotImplementedError(
                        f"pipeline×mp: flash_attention mask {mn!r} in "
                        f"stage {si} is mp-sharded; the additive mask "
                        f"must be replicated")
                if spec is not None and not (
                        len(spec) == 4 and spec[1] == "mp"
                        and all(s != "mp" for j, s in enumerate(spec)
                                if j != 1)):
                    raise NotImplementedError(
                        f"pipeline×mp: flash_attention in stage {si} "
                        f"reads q/k/v sharded on a non-heads dim "
                        f"({spec}); only heads-dim (Megatron) sharding "
                        f"rides through the fused kernel")
                for n in op.output_arg_names():
                    if spec is not None:
                        known[n] = spec
                    else:
                        known.pop(n, None)
                continue
            bad = sorted(n for n in op.input_arg_names() if has_mp(n))
            if bad:
                raise NotImplementedError(
                    f"pipeline×mp: op {op.type!r} in stage {si} reads "
                    f"mp-sharded value(s) {bad}; only the matmul/"
                    f"elementwise/activation family may touch sharded "
                    f"activations — end the sharded region with a "
                    f"row-parallel matmul (the Megatron pattern puts "
                    f"softmax/dropout/layer_norm after the mp reduce) "
                    f"or drop the partition rule for these weights")
            for n in op.output_arg_names():
                known.pop(n, None)
    return known


def analyze_stages(program, n_stages: int):
    """Partition forward ops into stages via op_device annotations.

    Untagged ops inherit the previous op's stage (build order), starting
    at stage 0.  Returns (stage_ops, boundary_vars): boundary_vars[s] is
    the LIST of activations stage s hands to later stages.
    """
    meta = getattr(program, "_pipeline", None)
    fwd_end = meta["fwd_end"] if meta else len(program.global_block.ops)
    ops = [op for op in program.global_block.ops[:fwd_end]
           if op.type not in ("feed", "fetch")]
    stage_ops: List[list] = [[] for _ in range(n_stages)]
    cur = 0
    for op in ops:
        dev = op.attr("op_device", None)
        if dev:
            if not str(dev).startswith("stage:"):
                raise ValueError(
                    f"op_device {dev!r} is not a pipeline annotation; use "
                    f"device_guard('stage:N')")
            s = int(str(dev).split(":", 1)[1])
            if s < cur:
                raise ValueError(
                    f"op {op.type!r} tagged stage {s} appears after stage "
                    f"{cur} ops; stages must be contiguous in build order")
            if s >= n_stages:
                raise ValueError(
                    f"op {op.type!r} tagged stage {s} but the mesh has only "
                    f"{n_stages} pipeline stages")
            cur = s
        stage_ops[cur].append(op)

    boundaries = []
    produced_upto = set()
    for s in range(n_stages - 1):
        produced_upto |= {n for op in stage_ops[s]
                          for n in op.output_arg_names()}
        consumed = set()
        for later in range(s + 1, n_stages):
            for op in stage_ops[later]:
                for n in op.input_arg_names():
                    if n in produced_upto:
                        consumed.add(n)
        # cumulative: vars produced at ANY stage <= s and consumed later
        # ride every intervening boundary (skip connections pass through)
        act = sorted(consumed)
        if not act:
            raise ValueError(
                f"pipeline stage boundary {s}->{s + 1} passes no tensors; "
                f"every stage must feed the next")
        boundaries.append(act)
    return stage_ops, boundaries


class PackPlan:
    """Stage-ownership of training state + its packed layout.

    Ownership (which var lives on which stage, how optimizer ops
    partition) is computed at compile time from the program alone;
    the byte layout (offsets/width) is filled in lazily on the first
    `ensure_packed` call, when the scope has concrete shapes.
    """

    def __init__(self, n_stages, owned_stage, params_by_stage,
                 stage_opt_ops, shared_opt_ops, stage_ops, boundaries,
                 mp_degree=1, tp_dims=None, mp_specs=None):
        self.n_stages = n_stages
        self.owned_stage: Dict[str, int] = owned_stage
        self.owned_names = frozenset(owned_stage)
        self.params_by_stage = params_by_stage
        self.stage_opt_ops = stage_opt_ops
        self.shared_opt_ops = shared_opt_ops
        # the forward stage partition the plan was derived from, so the
        # compiled fn uses the identical view instead of re-deriving one
        self.stage_ops = stage_ops
        self.boundaries = boundaries
        # dp×mp×pp composition: tensor-parallel degree, per-var sharded
        # dim of the owned state (params + inheriting slots), and the
        # strict mp-layout walk's final spec map (fetch validation)
        self.mp_degree = int(mp_degree)
        self.tp_dims: Dict[str, int] = dict(tp_dims or {})
        self.mp_specs: Dict[str, tuple] = dict(mp_specs or {})
        # filled by _build_layout on first ensure_packed; entry shapes
        # are LOCAL (per-mp-rank shard) shapes, gshapes the global ones
        self.entries = None  # per stage: [(name, off, size, lshape), ...]
        self.layout = None   # name -> (stage, off, size, lshape)
        self.gshapes: Dict[str, tuple] = {}
        self.width = None

    # -- layout --------------------------------------------------------
    def _local_shape(self, name, gshape):
        d = self.tp_dims.get(name)
        if d is None or self.mp_degree <= 1:
            return tuple(gshape)
        ls = list(gshape)
        ls[d] = int(ls[d]) // self.mp_degree
        return tuple(ls)

    def _build_layout(self, shapes: Dict[str, tuple]):
        entries = [[] for _ in range(self.n_stages)]
        layout = {}
        cursor = [0] * self.n_stages
        for n in sorted(self.owned_stage):
            s = self.owned_stage[n]
            gshape = tuple(shapes[n])
            shape = self._local_shape(n, gshape)
            size = 1
            for d in shape:
                size *= int(d)
            off = cursor[s]
            cursor[s] += size
            entries[s].append((n, off, size, shape))
            layout[n] = (s, off, size, shape)
            self.gshapes[n] = gshape
        self.entries = entries
        self.layout = layout
        self.width = max(cursor) if max(cursor) > 0 else 1

    # -- host-side pack ------------------------------------------------
    def ensure_packed(self, scope, mesh):
        """Pack owned scope vars into the sharded (S, W) buffer.

        No-op when the scope already holds the packed buffer and every
        owned var is a PackedParamRef view.  A concrete array over an
        owned name (fresh startup run, paddle.load restore) triggers a
        re-pack of those entries.
        """
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ..framework.scope import PackedParamRef

        concrete = {}
        for n in self.owned_stage:
            if not scope.has_var(n):
                raise RuntimeError(
                    f"pipeline state var {n!r} is not in the scope; run "
                    f"the startup program first")
            v = scope.get_var(n)
            if not isinstance(v, PackedParamRef):
                concrete[n] = np.asarray(v)
        has_buf = scope.has_var(PACKED_STATE_VAR)

        if self.layout is None:
            # shapes come from concrete arrays or from the ref views a
            # sibling plan (different fetch list, same program) installed
            shapes = {}
            for n in self.owned_stage:
                v = scope.get_var(n)
                dt = np.dtype(v.dtype)
                if dt != np.float32:
                    raise NotImplementedError(
                        f"pipeline per-stage state sharding requires "
                        f"float32 training state; {n!r} is {dt}")
                shapes[n] = tuple(int(d) for d in v.shape)
            self._build_layout(shapes)
        S, W, MP = self.n_stages, self.width, self.mp_degree
        buf_shape = (S, W) if MP <= 1 else (S, MP, W)
        if has_buf:
            have = tuple(scope.get_var(PACKED_STATE_VAR).shape)
            if have != buf_shape:
                raise RuntimeError(
                    f"existing packed pipeline buffer has shape "
                    f"{have}, expected {buf_shape}; the program's "
                    f"stage-owned state changed — rebuild the scope")
        if has_buf and not concrete:
            return

        buf = np.zeros(buf_shape, np.float32)
        if has_buf:
            buf[:] = np.asarray(scope.get_var(PACKED_STATE_VAR))
        elif len(concrete) != len(self.owned_stage):
            missing = sorted(self.owned_names - set(concrete))
            raise RuntimeError(
                f"pipeline state vars {missing} are packed views but no "
                f"packed buffer exists in this scope")
        for n, v in concrete.items():
            s, off, size, shape = self.layout[n]
            gshape = self.gshapes[n]
            if tuple(v.shape) != tuple(gshape):
                raise ValueError(
                    f"pipeline state var {n!r} has shape {v.shape}, "
                    f"expected {gshape}")
            v = v.astype(np.float32)
            if MP <= 1:
                buf[s, off:off + size] = v.ravel()
                continue
            d = self.tp_dims.get(n)
            for r in range(MP):
                if d is None:
                    shard = v  # replicated: same bytes on every mp rank
                else:
                    k = int(gshape[d]) // MP
                    sl = [slice(None)] * len(gshape)
                    sl[d] = slice(r * k, (r + 1) * k)
                    shard = v[tuple(sl)]
                buf[s, r, off:off + size] = shard.ravel()
        sharding = NamedSharding(mesh, P("pp") if MP <= 1
                                 else P("pp", "mp"))
        arr = jax.make_array_from_callback(
            buf_shape, sharding, lambda idx: buf[idx])
        scope.set_var(PACKED_STATE_VAR, arr)
        for n, (s, off, size, shape) in self.layout.items():
            scope.set_var(n, PackedParamRef(
                scope, PACKED_STATE_VAR, s, off, self.gshapes[n],
                np.float32, mp_degree=MP,
                mp_dim=self.tp_dims.get(n)))


def plan_packing(program, n_stages, state_in, state_out, pipe,
                 tp_plan=None):
    """Compute stage ownership of params + optimizer slots and partition
    the optimizer ops per stage (compile-time; shapes come later).

    ``tp_plan`` (the ShardingPropagationPass output on the post-pass
    program) turns on the dp×mp×pp composition: rule-matched owned vars
    are packed as per-mp-rank shards and the strict mp-flow walk
    validates that sharded activations only meet understood ops."""
    from ..framework.lowering import PSEUDO_OPS

    stage_ops, boundaries = analyze_stages(program, n_stages)
    block = program.global_block
    grad_of = {(p if isinstance(p, str) else p.name):
               (g if isinstance(g, str) else g.name)
               for p, g in pipe["params_grads"]}
    grad_names = set(grad_of.values())
    opt_ops = [op for op in block.ops[pipe["bwd_end"]:]
               if op.type not in PSEUDO_OPS]
    state_vars = set(state_in) | set(state_out)

    # each parameter is owned by the single stage whose forward reads it
    param_stage: Dict[str, int] = {}
    for s, ops in enumerate(stage_ops):
        reads = {n for op in ops for n in op.input_arg_names()}
        for p in grad_of:
            if p in reads:
                if p in param_stage and param_stage[p] != s:
                    raise NotImplementedError(
                        f"parameter {p!r} is read by pipeline stages "
                        f"{param_stage[p]} and {s}; shared (tied) "
                        f"parameters are not supported by the pipeline "
                        f"executor")
                param_stage.setdefault(p, s)
    unread = sorted(set(grad_of) - set(param_stage))
    if unread:
        raise ValueError(
            f"parameters {unread} are not read by any pipeline stage")

    # optimizer slots inherit the stage of the param their op updates;
    # fixpoint so slot-only ops (chained accumulators) resolve too
    owned_stage: Dict[str, int] = dict(param_stage)
    op_stage: Dict[int, int] = {}  # opt-op index -> stage
    pending = list(enumerate(opt_ops))
    while True:
        progressed = False
        still = []
        for idx, op in pending:
            names = set(op.input_arg_names()) | set(op.output_arg_names())
            stages = {owned_stage[n] for n in names if n in owned_stage}
            if len(stages) > 1:
                raise NotImplementedError(
                    f"optimizer op {op.type!r} touches state owned by "
                    f"stages {sorted(stages)}; cross-stage optimizer ops "
                    f"(e.g. global grad clipping) are not supported under "
                    f"pipeline state sharding")
            if stages:
                s = stages.pop()
                op_stage[idx] = s
                for n in op.output_arg_names():
                    if n in state_vars and n not in grad_names:
                        owned_stage[n] = s
                progressed = True
            else:
                still.append((idx, op))
        pending = still
        if not progressed or not pending:
            break
    # preserve PROGRAM ORDER inside each stage: ops resolved in a later
    # fixpoint round must not execute after ops they precede
    stage_opt_ops: List[list] = [
        [opt_ops[i] for i in sorted(op_stage) if op_stage[i] == s]
        for s in range(n_stages)]
    shared_opt_ops = [op for _, op in pending]

    # shared ops must be computable replicated: no stage-owned state, no
    # per-stage gradients, no temporaries produced by per-stage opt ops
    stage_temps = {n for ops in stage_opt_ops for op in ops
                   for n in op.output_arg_names()}
    for op in shared_opt_ops:
        ins = set(op.input_arg_names())
        bad = sorted(ins & (set(owned_stage) | grad_names | stage_temps))
        if bad:
            raise NotImplementedError(
                f"optimizer op {op.type!r} reads {bad} which live on "
                f"individual pipeline stages; global reductions over "
                f"stage-sharded state/gradients are not supported")

    # forward may read owned NON-param state only via the carried-state
    # path, never from the packed buffer
    fwd_reads = {n for ops in stage_ops for op in ops
                 for n in op.input_arg_names()}
    bad = sorted(fwd_reads & (set(owned_stage) - set(param_stage)))
    if bad:
        raise NotImplementedError(
            f"forward ops read optimizer-slot state {bad} which is "
            f"sharded per stage")

    params_by_stage = [[p for p in sorted(grad_of) if param_stage[p] == s]
                       for s in range(n_stages)]

    # dp×mp×pp: per-owned-var sharded dim from the tp plan + the strict
    # mp-flow validation of the staged forward
    mp_degree = 1
    tp_dims: Dict[str, int] = {}
    mp_specs: Dict[str, tuple] = {}
    if tp_plan is not None and tp_plan.mp_degree > 1:
        mp_degree = tp_plan.mp_degree
        for n in owned_stage:
            spec = tuple(tp_plan.specs.get(n, ()))
            dims = [i for i, x in enumerate(spec) if x == "mp"]
            if len(dims) > 1:
                raise NotImplementedError(
                    f"pipeline×mp: {n!r} is mp-sharded on several dims "
                    f"({spec}); one 'mp' dim per var is supported")
            if dims:
                tp_dims[n] = dims[0]
        mp_specs = _validate_mp_flow(block, stage_ops, tp_plan)

    return PackPlan(n_stages, owned_stage, params_by_stage, stage_opt_ops,
                    shared_opt_ops, stage_ops, boundaries,
                    mp_degree=mp_degree, tp_dims=tp_dims,
                    mp_specs=mp_specs)


def _plan_stage_scans(program, plan, extra_needed):
    """Scan-over-layers INSIDE each pipeline stage: detect isomorphic
    per-layer op runs in every stage's forward partition (and its
    optimizer partition) with the LayerScanPass machinery, and plan
    them for a trace-level ``lax.scan`` — the stage body is traced once
    per run instead of once per layer, so trace+compile cost per stage
    stays ~constant in stage depth while numerics are bitwise (same
    ops, same order, same RNG-split chain threaded through the carry).

    Returns ``(fwd_runs, opt_runs, policy)``; ``None`` lists when the
    scan gate (FLAGS_layer_scan / recompute_configs stamps) is off.
    Rejected runs fall back to the unrolled trace, counted
    ``pipeline_scan_skipped_<reason>``."""
    from ..framework.passes import LayerScanPass
    from ..monitor import stat_add, stat_set

    enabled, min_layers, policy = LayerScanPass._config(program)
    if not enabled:
        return None, None, ""
    lsp = LayerScanPass()
    block = program.global_block

    def plan_list(ops_seq, base_need):
        ops_list = list(ops_seq)
        runs = []
        for (start, L, M) in lsp._find_runs(block, ops_list, min_layers):
            cplan, reason = lsp._classify(ops_list, start, L, M)
            if cplan is None:
                stat_add("pipeline_scan_skipped")
                stat_add(f"pipeline_scan_skipped_{reason}")
                continue
            need = set(base_need)
            for i, op in enumerate(ops_list):
                if not (cplan.start <= i < cplan.end):
                    need.update(op.input_arg_names())
            # carry INTERMEDIATES never materialize per layer: a mid-
            # chain value consumed outside the run keeps it unrolled
            bad = False
            for (t, w) in cplan.carries:
                mem_in = [sg[t] for sg in cplan.sigmas]
                mem_out = [sg[w] for sg in cplan.sigmas]
                if (set(mem_in[1:]) | set(mem_out[:-1])) & need:
                    bad = True
                    break
            if bad:
                stat_add("pipeline_scan_skipped")
                stat_add("pipeline_scan_skipped_carry_read")
                continue
            ys_emit = []
            for fam in cplan.ys:
                idxs = [i for i, m in enumerate(fam["members"])
                        if m in need]
                if idxs:
                    ys_emit.append((fam, idxs))
            runs.append({"start": cplan.start, "end": cplan.end,
                         "plan": cplan, "ys_emit": ys_emit})
        return runs

    fwd_runs = [plan_list(plan.stage_ops[s], extra_needed)
                for s in range(plan.n_stages)]
    # optimizer partitions: every owned per-layer state member is read
    # back by the packed-row update, so all ys materialize
    opt_need = set(plan.owned_names) | set(extra_needed)
    opt_runs = [plan_list(plan.stage_opt_ops[s], opt_need)
                for s in range(plan.n_stages)]
    n_runs = sum(len(r) for r in fwd_runs) + sum(len(r) for r in opt_runs)
    stat_set("pipeline_scan_segments", n_runs)
    return fwd_runs, opt_runs, policy


def _emit_stage_scan(ctx, run, lower_one, policy):
    """Trace one planned isomorphic run as a single ``lax.scan`` over
    stacked per-layer values (stacking env entries at trace time keeps
    the op semantics byte-for-byte: each iteration lowers exactly the
    template ops the unrolled trace would, with the same key chain)."""
    import jax.numpy as jnp
    from jax import lax

    from ..framework.lowering import LoweringContext
    from ..ops.layer_scan import wrap_checkpoint

    plan = run["plan"]
    env = ctx.env
    carry_t = [t for t, _ in plan.carries]
    carry_w = [w for _, w in plan.carries]
    xs_tpls = [f["tpl"] for f in plan.xs]
    xs_stacks = tuple(
        jnp.stack([jnp.asarray(env[m]) for m in f["members"]])
        for f in plan.xs)
    shared_vals = {n: env[n] for n in plan.shared}
    init = tuple(jnp.asarray(env[t]) for t in carry_t)
    ys_emit = run["ys_emit"]
    ys_tpls = [f["tpl"] for f, _ in ys_emit]
    has_key = ctx.rng_key is not None
    consumed = [False]

    def body(carry, x):
        key, cvals = (carry[0], carry[1:]) if has_key else (None, carry)
        benv = dict(shared_vals)
        benv.update(zip(carry_t, cvals))
        if xs_tpls:
            benv.update(zip(xs_tpls, x))
        bctx = LoweringContext(ctx.block, benv, rng_key=key,
                               mesh=ctx.mesh, axis_env=ctx.axis_env,
                               ring_axes=ctx.ring_axes,
                               fold_axes=ctx.fold_axes)
        for top in plan.tpl:
            lower_one(bctx, top)
        consumed[0] = consumed[0] or bctx.rng_consumed
        ys = tuple(jnp.asarray(benv[t]) for t in ys_tpls)
        nc = tuple(benv[w] for w in carry_w)
        if has_key:
            new_key = bctx.rng_key if bctx.rng_consumed else key
            return (new_key,) + nc, ys
        return nc, ys

    body = wrap_checkpoint(body, policy or "")
    init_carry = ((ctx.rng_key,) + init) if has_key else init
    final, ys_stacks = lax.scan(body, init_carry,
                                xs_stacks if xs_stacks else None,
                                length=plan.M)
    if has_key:
        new_key, fvals = final[0], final[1:]
        if consumed[0]:
            ctx._rng = new_key
            ctx.rng_consumed = True
    else:
        fvals = final
    sigN = plan.sigmas[-1]
    for w, v in zip(carry_w, fvals):
        env[sigN[w]] = v
    for (fam, idxs), stack in zip(ys_emit, ys_stacks):
        for i in idxs:
            env[fam["members"][i]] = stack[i]


def build_pipeline_fn(program, mesh, feed_names, state_mut, state_const,
                      state_out, fetch_names, loss_name, params_grads,
                      n_microbatches, bwd_end, plan):
    """The compiled GPipe train step (plugs into Executor._compile).

    `state_mut` / `state_out` arrive WITH `PACKED_STATE_VAR` as their
    first entry and the stage-owned names already removed (the executor
    rewrites them via the PackPlan).  Signature matches the standard
    sharded path: (feed_vals, mut_vals, const_vals, rng) ->
    (fetches, new_state, rng).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from ..framework.lowering import (PSEUDO_OPS, LoweringContext,
                                      get_lowering)

    pp_axis = "pp"
    if pp_axis not in mesh.axis_names:
        raise ValueError(
            f"pipeline execution needs a 'pp' mesh axis; got "
            f"{mesh.axis_names}")
    dp_axis = "dp" if "dp" in mesh.axis_names else None
    dp_size = int(mesh.shape[dp_axis]) if dp_axis else 1
    # dp×mp×pp composition: the mp axis is live when the sharding pass
    # planned per-mp-rank shards (plan.mp_degree > 1); a mesh with an
    # 'mp' axis but no tp plan just replicates over it
    mp_axis = "mp" if (plan.mp_degree > 1
                       and "mp" in mesh.axis_names) else None
    if plan.mp_degree > 1 and mp_axis is None:
        raise ValueError(
            f"pipeline×mp: the sharding plan wants mp="
            f"{plan.mp_degree} but the mesh has no 'mp' axis "
            f"({mesh.axis_names})")
    if mp_axis and int(mesh.shape[mp_axis]) != plan.mp_degree:
        raise ValueError(
            f"pipeline×mp: mesh 'mp' axis has "
            f"{int(mesh.shape[mp_axis])} devices but the sharding plan "
            f"packed {plan.mp_degree}-way shards")
    S = int(mesh.shape[pp_axis])
    K = int(n_microbatches)
    stage_ops, boundaries = plan.stage_ops, plan.boundaries
    block = program.global_block
    assert state_mut and state_mut[0] == PACKED_STATE_VAR
    assert state_out and state_out[0] == PACKED_STATE_VAR
    rest_mut = state_mut[1:]
    rest_out = state_out[1:]

    from ..framework import flags as _flags
    from ..framework.passes import TP_CONSTRAINT_ATTR, decode_anchor
    from ..monitor import stat_set as _stat_set
    from ..observe import tracer as otrace
    from ..ops.collective_matmul import chunked_lower, f_identity, g_psum

    # GPipe's schedule cost, published for the overlap/telemetry plane:
    # of the K + S - 1 forward (and backward) ticks, S - 1 are fill/
    # drain bubbles on any given rank
    _stat_set("pp_stages", S)
    _stat_set("pp_bubble_fraction_ppm",
              int(round((S - 1) / float(K + S - 1) * 1e6)))

    grad_of = {(p if isinstance(p, str) else p.name):
               (g if isinstance(g, str) else g.name)
               for p, g in params_grads}

    # fetches: the loss plus any forward-produced activation
    producer_stage: Dict[str, int] = {}
    for s, ops in enumerate(stage_ops):
        for op in ops:
            for n in op.output_arg_names():
                producer_stage[n] = s  # last producer wins
    extra_fetches = [f for f in fetch_names if f != loss_name]
    for f in extra_fetches:
        if f not in producer_stage:
            raise NotImplementedError(
                f"pipeline fetch {f!r} is not produced by any forward "
                f"stage op; fetchable values are forward activations and "
                f"the loss")
    if plan.mp_specs:
        bad = [f for f in extra_fetches
               if any(x == "mp" for x in plan.mp_specs.get(f, ()))]
        if bad:
            raise NotImplementedError(
                f"pipeline×mp: fetches {bad} are mp-sharded "
                f"activations; fetch a value downstream of the "
                f"row-parallel reduce instead")

    # state written inside staged forwards (batch_norm running stats):
    # carried tick-to-tick on the owning stage's rank, published at the end
    state_out_set = set(rest_out)
    opt_writes = {n for ops in (plan.shared_opt_ops, *plan.stage_opt_ops)
                  for op in ops for n in op.output_arg_names()}
    carried_owner: Dict[str, int] = {}
    for s, ops in enumerate(stage_ops):
        for op in ops:
            for n in op.output_arg_names():
                if n in state_out_set and n not in plan.owned_names \
                        and n not in opt_writes:
                    carried_owner[n] = s
    carried_names = sorted(carried_owner)

    # scan-over-layers inside each stage (trace-level): names any run's
    # stacked outputs must still materialize into the env for
    scan_needed = set(carried_names) | set(fetch_names) | {loss_name}
    for b in boundaries:
        scan_needed.update(b)
    for ops_l in ([plan.shared_opt_ops] + list(plan.stage_opt_ops)):
        for op_ in ops_l:
            scan_needed.update(op_.input_arg_names())
    fwd_runs, opt_runs, scan_policy = _plan_stage_scans(
        program, plan, scan_needed)

    anchored = plan.mp_degree > 1 and mp_axis is not None
    cm_chunks = int(_flags.flag("collective_matmul_chunks") or 0)

    def _lower_one(ctx, op):
        """One op through its registered lowering, with the manual
        Megatron f/g handling at the sharding pass's anchors: a
        column-parallel matmul's row operand rides f (bwd mp-psum of
        dx), a contracted (partial) anchor's output rides g (fwd
        mp-psum) — optionally decomposed into latency-hiding
        collective-matmul chunks."""
        env2 = ctx.env
        try:
            ents = (op.attr(TP_CONSTRAINT_ATTR, []) or []) \
                if anchored else []
            if not ents:
                get_lowering(op.type)(ctx, op)
                return
            anchors = [decode_anchor(e) for e in ents]
            partials = {n for n, sp, p in anchors if p}
            cols = [n for n, sp, p in anchors
                    if not p and any(x == "mp" for x in sp)]
            wrapped = None
            if cols and op.type in _TP_MATMUL_TYPES:
                xn = op.inputs.get("X", [None])[0]
                if xn is not None and xn in env2 \
                        and xn not in op.output_arg_names() \
                        and not any(x == "mp" for x in
                                    plan.mp_specs.get(xn, ())):
                    # f is scoped to THIS op: each consumer of a
                    # replicated activation psums its own cotangent
                    # branch (psum(a)+psum(b) == psum(a+b))
                    wrapped = (xn, env2[xn])
                    env2[xn] = f_identity(env2[xn], mp_axis)
            try:
                done = False
                outs = op.output_arg_names()
                if partials and cm_chunks > 1 \
                        and op.type in _TP_MATMUL_TYPES \
                        and len(outs) == 1 and outs[0] in partials:
                    done = chunked_lower(
                        ctx, op, cm_chunks,
                        lambda v, _i: g_psum(v, mp_axis))
                if not done:
                    get_lowering(op.type)(ctx, op)
                    for n in partials:
                        if n in env2:
                            env2[n] = g_psum(env2[n], mp_axis)
            finally:
                if wrapped is not None:
                    env2[wrapped[0]] = wrapped[1]
        except Exception as e:
            site = op.callstack[-1] if op.callstack else "<unknown>"
            raise type(e)(
                f"while lowering pipeline op {op.type!r} (built at "
                f"{site}): {e}") from e

    def trace_ops(ops, env, rng_key=None, runs=None, stage=None):
        axes = (pp_axis,) \
            + ((mp_axis,) if mp_axis else ()) \
            + ((dp_axis,) if dp_axis else ())
        ctx = LoweringContext(block, env, rng_key=rng_key, mesh=mesh,
                              axis_env=axes,
                              fold_axes=(dp_axis,) if dp_axis else ())
        span = otrace.span("pipeline/stage", stage=stage,
                           ops=len(ops)) \
            if stage is not None else otrace.NULL_SPAN
        with span:
            if not runs:
                for op in ops:
                    _lower_one(ctx, op)
                return env
            ops_l = list(ops)
            run_at = {r["start"]: r for r in runs}
            i = 0
            while i < len(ops_l):
                r = run_at.get(i)
                if r is not None and all(
                        m in env for f_ in r["plan"].xs
                        for m in f_["members"]) \
                        and all(n in env for n in r["plan"].shared) \
                        and all(t in env for t, _ in r["plan"].carries):
                    _emit_stage_scan(ctx, r, _lower_one, scan_policy)
                    i = r["end"]
                else:
                    # an input the plan expected is absent from THIS
                    # env (e.g. a probe with a reduced view): the run
                    # traces unrolled — numerics identical either way
                    _lower_one(ctx, ops_l[i])
                    i += 1
        return env

    def unpack_stage(s, buf):
        """Reinterpret the local packed row with stage s's layout."""
        return {n: buf[off:off + size].reshape(shape)
                for (n, off, size, shape) in plan.entries[s]}

    def traced(feed_vals, mut_vals, const_vals, rng):
        # local packed-state shard -> (W,): (1, W) over P('pp'), or
        # (1, 1, W) over P('pp', 'mp') in the dp×mp×pp composition
        lbuf = mut_vals[0][0]
        if mp_axis:
            lbuf = lbuf[0]
        base_env = {}
        base_env.update(zip(rest_mut, mut_vals[1:]))
        base_env.update(zip(state_const, const_vals))
        full_feeds = dict(zip(feed_names, feed_vals))
        r = lax.axis_index(pp_axis)

        # micro-batch every feed: (B, ...) -> (K, B//K, ...)
        mb_feeds = {}
        for n, v in full_feeds.items():
            b = v.shape[0]
            if b % K:
                raise ValueError(
                    f"feed {n!r} batch {b} not divisible by micro_batch "
                    f"count {K}")
            mb_feeds[n] = v.reshape((K, b // K) + v.shape[1:])

        # ---- probe boundary + fetch structures stage by stage -----------
        mb_structs = {n: jax.ShapeDtypeStruct((v.shape[1],) + v.shape[2:],
                                              v.dtype)
                      for n, v in mb_feeds.items()}
        fetch_by_stage = [[f for f in extra_fetches
                           if producer_stage[f] == s] for s in range(S)]

        def probe_stage(s, in_structs):
            def f(acts_in):
                env = dict(base_env)
                for (n, off, size, shape) in plan.entries[s]:
                    env[n] = jnp.zeros(shape, jnp.float32)
                for n, sd in mb_structs.items():
                    env[n] = jnp.zeros(sd.shape, sd.dtype)
                if s > 0:
                    env.update(dict(zip(boundaries[s - 1], acts_in)))
                trace_ops(stage_ops[s], env,
                          rng_key=jax.random.PRNGKey(0),
                          runs=fwd_runs[s] if fwd_runs else None)
                bnd = tuple(jnp.asarray(env[n]) for n in boundaries[s]) \
                    if s < S - 1 else ()
                fts = tuple(jnp.asarray(env[f]) for f in fetch_by_stage[s])
                return bnd, fts

            dummy = tuple(jnp.zeros(sd.shape, sd.dtype)
                          for sd in (in_structs or ()))
            return jax.eval_shape(f, dummy)

        bnd_structs = []  # per boundary: tuple of ShapeDtypeStructs
        fetch_structs: Dict[str, object] = {}
        prev = None
        for s in range(S):
            prev, fstructs = probe_stage(s, prev)
            if s < S - 1:
                bnd_structs.append(prev)
            for f, sd in zip(fetch_by_stage[s], fstructs):
                fetch_structs[f] = sd
        for structs, names in zip(bnd_structs, boundaries):
            for sd, n in zip(structs, names):
                if not jnp.issubdtype(sd.dtype, jnp.floating):
                    raise NotImplementedError(
                        f"pipeline boundary tensor {n!r} has non-float "
                        f"dtype {sd.dtype}; route integer data to every "
                        f"stage via feeds instead")

        # classify fetches: scalar -> mean over microbatches (loss-like);
        # per-microbatch batched -> concatenated over microbatches
        mb_b = next(iter(mb_structs.values())).shape[0] if mb_structs else 0
        scalar_fetches, batched_fetches = [], []
        for f in extra_fetches:
            sd = fetch_structs[f]
            if sd.shape == ():
                if not jnp.issubdtype(sd.dtype, jnp.floating):
                    raise NotImplementedError(
                        f"pipeline scalar fetch {f!r} must be floating "
                        f"point, got {sd.dtype}")
                scalar_fetches.append(f)
            elif sd.shape and sd.shape[0] == mb_b:
                batched_fetches.append(f)
            else:
                raise NotImplementedError(
                    f"pipeline fetch {f!r} has per-microbatch shape "
                    f"{sd.shape}, which is neither a scalar nor batched "
                    f"over the micro-batch dim ({mb_b})")

        # ---- flat f32 carrier buffer, padded to the widest boundary -----
        def _size(sd):
            n = 1
            for d in sd.shape:
                n *= int(d)
            return n

        widths = [sum(_size(sd) for sd in structs)
                  for structs in bnd_structs]
        width = max(widths) if widths else 1
        zero_act = jnp.zeros((width,), jnp.float32)

        def pack(s, vals):
            flat = [jnp.ravel(v).astype(jnp.float32) for v in vals]
            buf = jnp.concatenate(flat) if flat else zero_act
            return jnp.pad(buf, (0, width - buf.shape[0]))

        def unpack(s, buf):
            vals = []
            off = 0
            for sd in bnd_structs[s]:
                n = _size(sd)
                vals.append(buf[off:off + n].reshape(sd.shape)
                            .astype(sd.dtype))
                off += n
            return vals

        def stage_key(rng_key, s, mb_idx):
            # deterministic per (stage, microbatch): the backward vjp
            # replays the forward with the same key -> identical dropout
            # masks (the correctness crux of RNG under GPipe)
            return jax.random.fold_in(jax.random.fold_in(rng_key, mb_idx), s)

        zero_fetches = tuple(jnp.zeros(fetch_structs[f].shape,
                                       fetch_structs[f].dtype)
                             for f in extra_fetches)

        def stage_fwd(s, buf, carried, act_buf, mb_idx, rng_key):
            """Uniform output across branches:
            (out_buf, loss, fetches, new_carried)."""
            env = dict(base_env)
            env.update(carried)
            env.update({p: v for p, v in unpack_stage(s, buf).items()
                        if p in grad_of})
            for n, v in mb_feeds.items():
                env[n] = lax.dynamic_index_in_dim(v, mb_idx, 0,
                                                  keepdims=False)
            if s > 0:
                env.update(dict(zip(boundaries[s - 1], unpack(s - 1, act_buf))))
            trace_ops(stage_ops[s], env, rng_key=stage_key(rng_key, s, mb_idx),
                      runs=fwd_runs[s] if fwd_runs else None, stage=s)
            new_carried = {
                n: (env[n] if carried_owner[n] == s else carried[n])
                for n in carried_names
            }
            fts = tuple(
                (jnp.asarray(env[f]).astype(fetch_structs[f].dtype)
                 if producer_stage[f] == s else z)
                for f, z in zip(extra_fetches, zero_fetches))
            if s < S - 1:
                out_buf = pack(s, [env[n] for n in boundaries[s]])
                return out_buf, jnp.zeros((), jnp.float32), fts, new_carried
            loss = jnp.asarray(env[loss_name], jnp.float32).reshape(())
            return zero_act, loss, fts, new_carried

        branches = [
            (lambda buf, c, a, i, k, s=s: stage_fwd(s, buf, c, a, i, k))
            for s in range(S)
        ]

        def switch_fwd(buf, carried, act_buf, mb_idx, rng_key):
            return lax.switch(r, branches, buf, carried, act_buf, mb_idx,
                              rng_key)

        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        bwd_perm = [(i + 1, i) for i in range(S - 1)]

        # ---- forward fill (K + S - 1 ticks) -----------------------------
        T = K + S - 1
        saved_in = jnp.zeros((K, width), jnp.float32)
        losses = jnp.zeros((K,), jnp.float32)
        carried = {n: base_env[n] for n in carried_names}
        fetch_bufs = {f: jnp.zeros((K,) + tuple(fetch_structs[f].shape),
                                   fetch_structs[f].dtype)
                      for f in batched_fetches}
        scalar_acc = {f: jnp.zeros((), fetch_structs[f].dtype)
                      for f in scalar_fetches}
        recv = zero_act
        for t in range(T):
            mb = jnp.clip(t - r, 0, K - 1)
            active = jnp.logical_and(t - r >= 0, t - r < K)
            act_out, loss_mb, fts, new_carried = switch_fwd(
                lbuf, carried, recv, mb, rng)
            carried = {
                n: jnp.where(active, new_carried[n], carried[n])
                for n in carried_names
            }
            # remember this tick's stage INPUT for the backward vjp
            prev = lax.dynamic_index_in_dim(saved_in, mb, 0, keepdims=False)
            upd = jnp.where(active, recv, prev)
            saved_in = lax.dynamic_update_index_in_dim(saved_in, upd, mb, 0)
            losses = losses.at[mb].set(
                jnp.where(active, loss_mb, losses[mb]))
            for f, v in zip(extra_fetches, fts):
                if f in fetch_bufs:
                    prevf = lax.dynamic_index_in_dim(fetch_bufs[f], mb, 0,
                                                     keepdims=False)
                    fetch_bufs[f] = lax.dynamic_update_index_in_dim(
                        fetch_bufs[f], jnp.where(active, v, prevf), mb, 0)
                else:
                    scalar_acc[f] = scalar_acc[f] + jnp.where(
                        active, v, jnp.zeros_like(v))
            send = jnp.where(active, act_out, zero_act)
            recv = lax.ppermute(send, pp_axis, fwd_perm)

        # ---- backward drain (K + S - 1 ticks) ---------------------------
        # backward replays the forward with the SAME carried snapshot; the
        # vjp does not need exact per-tick stats (grads of running-stat
        # updates are zero: they are stop-gradient outputs)
        def stage_bwd(buf, act_in, mb_idx, g_act, g_loss):
            def f(buf_, act_in_):
                out_buf, loss, _, _ = switch_fwd(buf_, carried, act_in_,
                                                 mb_idx, rng)
                return out_buf, loss

            _, vjp = jax.vjp(f, buf, act_in)
            gb, gact = vjp((g_act, g_loss))
            return gb, gact

        grad_acc = jnp.zeros_like(lbuf)
        g_recv = zero_act
        for u in range(T):
            m = jnp.clip(u - (S - 1 - r), 0, K - 1)
            active = jnp.logical_and(u - (S - 1 - r) >= 0,
                                     u - (S - 1 - r) < K)
            is_last = r == S - 1
            g_loss = jnp.where(jnp.logical_and(active, is_last),
                               jnp.float32(1.0 / K), 0.0)
            g_act = jnp.where(is_last, zero_act, g_recv)
            act_in = lax.dynamic_index_in_dim(saved_in, m, 0,
                                              keepdims=False)
            gb, gact = stage_bwd(lbuf, act_in, m, g_act, g_loss)
            # where-select, not multiply: an inf/NaN jacobian at a
            # zero-filled inactive tick must not poison the accumulator
            grad_acc = grad_acc + jnp.where(active, gb,
                                            jnp.zeros_like(gb))
            g_send = jnp.where(active, gact, zero_act)
            g_recv = lax.ppermute(g_send, pp_axis, bwd_perm)

        # packed per-stage grads stay on their owning rank (that is the
        # memory point of PP); only dp replicas reduce
        if dp_axis:
            grad_acc = lax.psum(grad_acc, dp_axis) / dp_size

        # publish carried state from its owning rank (other ranks still
        # hold the initial value); under dp the shards saw different data
        # so running stats are pmean'd — same approximation sync-free BN
        # makes in the reference's multi-device path
        final_carried = {}
        for n in carried_names:
            owner = carried_owner[n]
            v = carried[n]
            picked = jnp.where(r == owner, v, jnp.zeros_like(v))
            out = lax.psum(picked, pp_axis)
            if dp_axis:
                out = lax.pmean(out, dp_axis)
            final_carried[n] = out

        # ---- optimizer: shared ops replicated, stage ops switched -------
        env_shared = dict(base_env)
        env_shared.update(final_carried)
        trace_ops(plan.shared_opt_ops, env_shared)

        def opt_branch(s):
            def f(buf, gbuf):
                env = dict(env_shared)
                env.update(unpack_stage(s, buf))
                for p in plan.params_by_stage[s]:
                    _, off, size, shape = plan.layout[p]
                    env[grad_of[p]] = gbuf[off:off + size].reshape(shape)
                trace_ops(plan.stage_opt_ops[s], env,
                          runs=opt_runs[s] if opt_runs else None, stage=s)
                newb = buf
                for (n, off, size, shape) in plan.entries[s]:
                    newb = newb.at[off:off + size].set(
                        jnp.ravel(env[n]).astype(jnp.float32))
                return newb
            return f

        new_buf = lax.switch(r, [opt_branch(s) for s in range(S)],
                             lbuf, grad_acc)

        # full-batch mean loss, present on the last rank; psum-broadcast
        loss_sum = jnp.where(r == S - 1, losses.sum(), 0.0)
        mean_loss = lax.psum(loss_sum, pp_axis) / K
        if dp_axis:
            mean_loss = lax.pmean(mean_loss, dp_axis)

        # assemble fetches in fetch_names order
        computed = {}
        for f in scalar_fetches:
            v = lax.psum(scalar_acc[f], pp_axis) / K
            if dp_axis:
                v = lax.pmean(v, dp_axis)
            computed[f] = v
        for f in batched_fetches:
            full = lax.psum(fetch_bufs[f], pp_axis)
            full = full.reshape((-1,) + tuple(fetch_structs[f].shape[1:]))
            if dp_axis:
                full = lax.all_gather(full, dp_axis, axis=0, tiled=True)
            computed[f] = full
        fetches = tuple(mean_loss if f == loss_name else computed[f]
                        for f in fetch_names)

        out_buf = new_buf[None, None, :] if mp_axis else new_buf[None, :]
        new_state = (out_buf,) \
            + tuple(env_shared[n] for n in rest_out)
        new_rng = jax.random.split(rng, 2)[0]
        return fetches, new_state, new_rng

    in_feed_specs = tuple(
        (P(dp_axis) if dp_axis else P()) for _ in feed_names)
    buf_spec = P(pp_axis, mp_axis) if mp_axis else P(pp_axis)
    return shard_map(
        traced,
        mesh=mesh,
        in_specs=(in_feed_specs,
                  (buf_spec,) + tuple(P() for _ in rest_mut),
                  tuple(P() for _ in state_const),
                  P()),
        out_specs=(tuple(P() for _ in fetch_names),
                   (buf_spec,) + tuple(P() for _ in rest_out),
                   P()),
        check_vma=False,
    )
