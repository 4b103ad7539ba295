"""Meta-optimizers: strategy-driven optimizer/program rewrites.

Role parity: reference fleet/meta_optimizers/ (13 classes) + the
StrategyCompiler chain (fleet/base/strategy_compiler.py:89,112).  Each
meta-optimizer declares _can_apply() against the DistributedStrategy and
wraps minimize; the compiler orders the applicable ones and the last
graph-level one performs the collective transpile.
"""
from __future__ import annotations

from ...framework.program import GRAD_SUFFIX
from .collective_transpiler import GradAllReduce, LocalSGD, _last_writer_map


class MetaOptimizerBase:
    can_be_last = False

    def __init__(self, inner_opt):
        self.inner_opt = inner_opt
        self.role_maker = None
        self.user_strategy = None

    def _set_basic_info(self, loss, role_maker, user_opt, user_strategy):
        self.loss = loss
        self.role_maker = role_maker
        self.user_opt = user_opt
        self.user_strategy = user_strategy

    def _can_apply(self) -> bool:
        return False

    def _nranks(self):
        from ..parallel_env import get_world_size

        return get_world_size()

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        return self.inner_opt.minimize(loss, startup_program, parameter_list,
                                       no_grad_set)

    # delegation so meta-optimizers compose (a wrapping meta-opt may call
    # backward/apply_gradients on its inner chain)
    def backward(self, *args, **kwargs):
        return self.inner_opt.backward(*args, **kwargs)

    def apply_gradients(self, params_grads):
        return self.inner_opt.apply_gradients(params_grads)

    def __getattr__(self, name):
        if name == "inner_opt":  # not yet set (unpickling/deepcopy)
            raise AttributeError(name)
        return getattr(self.inner_opt, name)


class LarsMetaOptimizer(MetaOptimizerBase):
    """Swap Momentum for LARS (reference lars_optimizer.py)."""

    def _can_apply(self):
        from ...optimizer.static_opt import MomentumOptimizer

        return (self.user_strategy.lars
                and isinstance(self.inner_opt, MomentumOptimizer))

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ...optimizer.static_opt import LarsMomentumOptimizer

        cfg = self.user_strategy.lars_configs
        opt = LarsMomentumOptimizer(
            learning_rate=self.inner_opt._learning_rate,
            momentum=getattr(self.inner_opt, "_momentum", 0.9),
            lars_coeff=cfg["lars_coeff"],
            lars_weight_decay=cfg["lars_weight_decay"],
            regularization=self.inner_opt.regularization,
            grad_clip=self.inner_opt._grad_clip)
        return opt.minimize(loss, startup_program, parameter_list, no_grad_set)


class LambMetaOptimizer(MetaOptimizerBase):
    """Swap Adam for LAMB (reference lamb_optimizer.py)."""

    def _can_apply(self):
        from ...optimizer.static_opt import AdamOptimizer

        return (self.user_strategy.lamb
                and isinstance(self.inner_opt, AdamOptimizer))

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ...optimizer.static_opt import LambOptimizer

        cfg = self.user_strategy.lamb_configs
        opt = LambOptimizer(
            learning_rate=self.inner_opt._learning_rate,
            beta1=getattr(self.inner_opt, "_beta1", 0.9),
            beta2=getattr(self.inner_opt, "_beta2", 0.999),
            epsilon=getattr(self.inner_opt, "_epsilon", 1e-6),
            lamb_weight_decay=cfg["lamb_weight_decay"],
            regularization=self.inner_opt.regularization,
            grad_clip=self.inner_opt._grad_clip)
        return opt.minimize(loss, startup_program, parameter_list, no_grad_set)


class AMPMetaOptimizer(MetaOptimizerBase):
    """Mixed precision (reference amp_optimizer.py): wrap the inner
    optimizer with the static AMP decorator — program rewrite inserting
    bf16/fp16 casts per white/black lists, plus dynamic loss scaling in
    fp16 mode (amp/static_amp.py)."""

    def _can_apply(self):
        return self.user_strategy.amp

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ...amp.lists import AutoMixedPrecisionLists
        from ...amp.static_amp import decorate

        cfg = self.user_strategy.amp_configs
        lists = AutoMixedPrecisionLists(
            custom_white_list=cfg.get("custom_white_list") or None,
            custom_black_list=cfg.get("custom_black_list") or None,
            custom_black_varnames=cfg.get("custom_black_varnames") or None)
        wrapped = decorate(
            self.inner_opt,
            amp_lists=lists,
            init_loss_scaling=float(cfg.get("init_loss_scaling", 2.0 ** 15)),
            incr_every_n_steps=int(cfg.get("incr_every_n_steps", 1000)),
            decr_every_n_nan_or_inf=int(cfg.get("decr_every_n_nan_or_inf", 2)),
            incr_ratio=float(cfg.get("incr_ratio", 2.0)),
            decr_ratio=float(cfg.get("decr_ratio", 0.5)),
            use_dynamic_loss_scaling=bool(
                cfg.get("use_dynamic_loss_scaling", True)),
            # TPU-native default: bf16, no loss scaling
            use_bf16=bool(cfg.get("use_bf16", True)))
        if not wrapped._use_bf16 and not getattr(
                self.inner_opt, "supports_grad_transform", False):
            # fp16 mode drives backward/apply_gradients directly; a
            # DIRECT gradient-merge inner composes via the grad-transform
            # hook (static_amp routes unscale + scaling-state updates
            # through the merge mask), but a merge buried deeper in the
            # chain would be silently bypassed — refuse that loudly
            o = self.inner_opt
            while isinstance(o, MetaOptimizerBase):
                if isinstance(o, GradientMergeMetaOptimizer):
                    raise NotImplementedError(
                        "amp (fp16 + loss scaling) composes with "
                        "gradient_merge only when gradient_merge is the "
                        "direct inner optimizer; use bf16 amp "
                        "(amp_configs={'use_bf16': True}, the TPU "
                        "default) for this chain")
                o = o.inner_opt
        return wrapped.minimize(loss, startup_program, parameter_list,
                                no_grad_set)


class RecomputeMetaOptimizer(MetaOptimizerBase):
    """Activation recompute (reference recompute_optimizer.py +
    backward.py:689): user-marked checkpoint vars partition the forward;
    append_backward re-emits each segment behind a `recompute_barrier`
    (lax.optimization_barrier CSE fence) so XLA recomputes activations in
    the backward instead of keeping them alive.

    Scan-over-layers extras (recompute_configs ``policy`` /
    ``scan_layers``): stamped AFTER the inner minimize onto the
    program's optimizer ops (``__layer_scan__`` /
    ``__layer_scan_policy__`` — attrs, so the contract survives
    clone/proto round-trips AND re-keys every executor cache via the
    fingerprint).  They turn the executor-side LayerScanPass on for
    this program and pick the ``jax.checkpoint`` remat policy its scan
    bodies are wrapped in — extending the barrier-based recompute
    support to XLA rematerialization choices per repeated block."""

    def _can_apply(self):
        return self.user_strategy.recompute

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ...framework.passes import (LAYER_SCAN_ATTR,
                                         LAYER_SCAN_POLICY_ATTR)
        from ...ops.layer_scan import REMAT_POLICIES

        cfg = self.user_strategy.recompute_configs
        ckpts = list(cfg.get("checkpoints", []))
        policy = str(cfg.get("policy") or "")
        scan_layers = int(cfg.get("scan_layers") or 0)
        if policy and policy not in REMAT_POLICIES:
            raise ValueError(
                f"recompute_configs['policy'] must be one of "
                f"{sorted(REMAT_POLICIES)}, got {policy!r}")
        if not ckpts and not (policy or scan_layers):
            raise ValueError(
                "strategy.recompute=True needs recompute_configs with "
                "'checkpoints': [var_names] (barrier-based recompute), "
                "'scan_layers': N and/or 'policy': <remat policy> "
                "(scan-over-layers), or both")
        prog = loss.block.program
        if ckpts:
            prog._recompute_checkpoints = ckpts
        ret = self.inner_opt.minimize(loss, startup_program, parameter_list,
                                      no_grad_set)
        if policy or scan_layers:
            stamped = False
            for op in prog.global_block.ops:
                if op.type in _OPTIMIZER_OP_TYPES:
                    if scan_layers:
                        op.attrs[LAYER_SCAN_ATTR] = scan_layers
                    if policy:
                        op.attrs[LAYER_SCAN_POLICY_ATTR] = policy
                    stamped = True
            if not stamped:
                raise ValueError(
                    "recompute_configs scan_layers/policy found no "
                    "optimizer ops to stamp; minimize() must build the "
                    "training program first")
            prog._bump()
        return ret


class GradientMergeMetaOptimizer(MetaOptimizerBase):
    """Accumulate grads K steps, apply the update on every K-th step
    (reference GradientMergeOptimizer, fluid/optimizer.py:5025).

    TPU-native: no conditional_block — the update runs every step but is
    masked: merged_grad = acc * mask (mask = 1 on the K-th step, else 0),
    and every state var written by the optimizer ops is snapshot before /
    select-restored after, so momentum/adam state only advances on real
    update steps.  XLA fuses the selects; there is no control-flow
    divergence on device."""

    supports_grad_transform = True  # fp16-AMP composes through the mask

    def _can_apply(self):
        return self.user_strategy.gradient_merge

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_transform=None):
        from ...framework.program import default_startup_program
        from ...initializer import ConstantInitializer
        from ...framework import unique_name

        cfg = self.user_strategy.gradient_merge_configs
        k = int(cfg.get("k_steps", 1))
        avg = bool(cfg.get("avg", True))
        if k <= 1:
            if grad_transform is None:
                return self.inner_opt.minimize(loss, startup_program,
                                               parameter_list, no_grad_set)
            # degenerate merge still owes the caller its transform (fp16
            # AMP's unscale + overflow check ride it — dropping it would
            # apply loss-scaled gradients)
            pgs = self.inner_opt.backward(loss, startup_program,
                                          parameter_list, no_grad_set)
            pgs = grad_transform(pgs)
            return self.inner_opt.apply_gradients(pgs), pgs

        params_grads = self.inner_opt.backward(
            loss, startup_program, parameter_list, no_grad_set)
        block = loss.block.program.global_block
        startup = startup_program or default_startup_program()

        def persistent(name, shape, value):
            v = block.create_var(name=name, shape=list(shape),
                                 dtype="float32", persistable=True,
                                 stop_gradient=True)
            sv = startup.global_block.create_var(
                name=name, shape=list(shape), dtype="float32",
                persistable=True)
            ConstantInitializer(value)(sv, startup.global_block)
            return v

        step = persistent(unique_name.generate("gm_step"), [1], 0.0)
        block.append_op("increment", {"X": [step.name]},
                        {"Out": [step.name]}, {"step": 1.0})
        k_const = block.create_var(name=unique_name.generate("gm_k"),
                                   shape=[1], dtype="float32",
                                   stop_gradient=True)
        block.append_op("fill_constant", {}, {"Out": [k_const.name]},
                        {"shape": [1], "dtype": "float32", "value": float(k)})
        cond = block.create_var(name=unique_name.generate("gm_cond"),
                                shape=[1], dtype="bool", stop_gradient=True)
        block.append_op("equal", {"X": [step.name], "Y": [k_const.name]},
                        {"Out": [cond.name]})
        mask = block.create_var(name=unique_name.generate("gm_mask"),
                                shape=[1], dtype="float32",
                                stop_gradient=True)
        block.append_op("cast", {"X": [cond.name]}, {"Out": [mask.name]},
                        {"out_dtype": "float32"})
        # step wraps back to 0 on update steps: step *= (1 - mask)
        inv = block.create_var(name=unique_name.generate("gm_inv"),
                               shape=[1], dtype="float32",
                               stop_gradient=True)
        block.append_op("scale", {"X": [mask.name]}, {"Out": [inv.name]},
                        {"scale": -1.0, "bias": 1.0, "bias_after_scale": True})
        block.append_op("elementwise_mul",
                        {"X": [step.name], "Y": [inv.name]},
                        {"Out": [step.name]}, {"axis": -1})

        merged = []
        acc_names = []
        for p, g in params_grads:
            acc = persistent(unique_name.generate(p.name + "_gm_acc"),
                             p.shape, 0.0)
            acc_names.append(acc.name)
            # __gm_grad__ marks the accumulate op for the sharding
            # transpiler (an op attr, not a python side channel, so the
            # linkage survives clone/proto round-trips like
            # __sharded_accumulators__ does)
            block.append_op("elementwise_add",
                            {"X": [acc.name], "Y": [g.name]},
                            {"Out": [acc.name]},
                            {"axis": -1, "__gm_grad__": g.name})
            mg = block.create_var(name=unique_name.generate(g.name + ".gm"),
                                  shape=list(p.shape), dtype="float32",
                                  stop_gradient=True)
            block.append_op("elementwise_mul",
                            {"X": [acc.name], "Y": [mask.name]},
                            {"Out": [mg.name]}, {"axis": -1})
            if avg:
                block.append_op("scale", {"X": [mg.name]}, {"Out": [mg.name]},
                                {"scale": 1.0 / k, "bias": 0.0,
                                 "bias_after_scale": True})
            merged.append((p, block.var(mg.name)))

        # optimizer ops run every step on the masked grad; snapshot every
        # state var they overwrite and select-restore on non-update steps.
        # The mark sits BEFORE the grad transform so state the transform
        # writes (e.g. fp16-AMP's loss-scaling counters, which would
        # otherwise advance on masked zero-grads every step) is snapshot
        # and select-restored exactly like optimizer state.
        mark = len(block.ops)
        if grad_transform is not None:
            merged = grad_transform(merged)
        opt_ops = self.inner_opt.apply_gradients(merged)
        appended = block.ops[mark:]
        state_names = []
        seen = set()
        for op in appended:
            for n in op.output_arg_names():
                if n in seen:
                    continue
                var = block._find_var_recursive(n)
                if var is not None and var.persistable:
                    seen.add(n)
                    state_names.append(n)
        backups = {}
        insert_at = mark
        for n in state_names:
            b = n + ".gm_backup"
            var = block._find_var_recursive(n)
            block.create_var(name=b, shape=list(var.shape), dtype=var.dtype,
                             stop_gradient=True)
            from ...framework.program import Operator

            bop = Operator(block, "assign", {"X": [n]}, {"Out": [b]})
            block.ops.insert(insert_at, bop)
            insert_at += 1
            backups[n] = b
        for n, b in backups.items():
            # n = mask*n_updated + (1-mask)*backup
            upd = n + ".gm_upd"
            var = block._find_var_recursive(n)
            block.create_var(name=upd, shape=list(var.shape),
                             dtype=var.dtype, stop_gradient=True)
            block.append_op("elementwise_mul", {"X": [n], "Y": [mask.name]},
                            {"Out": [upd]}, {"axis": -1})
            keep = b + ".keep"
            block.create_var(name=keep, shape=list(var.shape),
                             dtype=var.dtype, stop_gradient=True)
            block.append_op("elementwise_mul", {"X": [b], "Y": [inv.name]},
                            {"Out": [keep]}, {"axis": -1})
            block.append_op("elementwise_add", {"X": [upd], "Y": [keep]},
                            {"Out": [n]}, {"axis": -1})

        # accumulators reset after an applied update: acc *= (1 - mask)
        for acc_name in acc_names:
            block.append_op("elementwise_mul",
                            {"X": [acc_name], "Y": [inv.name]},
                            {"Out": [acc_name]}, {"axis": -1})
        loss.block.program._bump()
        return opt_ops, params_grads


class DGCMetaOptimizer(MetaOptimizerBase):
    """Deep gradient compression (reference
    fleet/meta_optimizers/dgc_optimizer.py + operators/dgc_op.cc):
    per-param momentum/residual accumulators feed a top-k sparsifying
    `dgc` op between backward and the optimizer apply; the sparsified
    grad is what rides the data-parallel allreduce.

    Pair with a plain SGD inner optimizer: the momentum correction
    lives INSIDE the dgc op's U accumulator (the reference's
    DGCMomentumOptimizer collapses both for the same reason — applying
    an outer momentum too would double it).

    Known simplification: the sparsity ratio is CONSTANT — only
    ``dgc_configs["sparsity"][0]`` is honored.  The reference ramps
    sparsity over ``rampup_step`` period steps (dgc_optimizer.py walks
    the sparsity list as warmup progresses); until that period-sparsity
    ramp lands here, pre-rampup steps pass dense grads through
    untouched (see the ``dgc`` lowering's early-return contract) and
    post-rampup steps jump straight to the final ratio."""

    def _can_apply(self):
        return self.user_strategy.dgc

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ...framework import unique_name
        from ...framework.program import default_startup_program
        from ...initializer import ConstantInitializer

        cfg = self.user_strategy.dgc_configs or {}
        ratio = 1.0 - float((cfg.get("sparsity") or [0.999])[0])
        rampup_begin = float(cfg.get("rampup_begin_step", 0))
        m = 0.9  # reference DGCMomentumOptimizer default; DGCConfig
        # carries no momentum field (distributed_strategy.proto)

        params_grads = self.inner_opt.backward(
            loss, startup_program, parameter_list, no_grad_set)
        block = loss.block.program.global_block
        startup = startup_program or default_startup_program()

        def persistent(name, shape, value):
            v = block.create_var(name=name, shape=list(shape),
                                 dtype="float32", persistable=True,
                                 stop_gradient=True)
            sv = startup.global_block.create_var(
                name=name, shape=list(shape), dtype="float32",
                persistable=True)
            ConstantInitializer(value)(sv, startup.global_block)
            return v

        step = persistent(unique_name.generate("dgc_step"), [1], 0.0)
        block.append_op("increment", {"X": [step.name]},
                        {"Out": [step.name]}, {"step": 1.0})

        compressed = []
        for p, g in params_grads:
            u = persistent(unique_name.generate(p.name + "_dgc_u"),
                           p.shape, 0.0)
            v = persistent(unique_name.generate(p.name + "_dgc_v"),
                           p.shape, 0.0)
            enc = block.create_var(
                name=unique_name.generate(g.name + ".dgc"),
                shape=list(p.shape), dtype="float32", stop_gradient=True)
            block.append_op(
                "dgc",
                {"Grad": [g.name], "U": [u.name], "V": [v.name],
                 "CurrentStep": [step.name]},
                {"U_out": [u.name], "V_out": [v.name],
                 "EncodeGrad": [enc.name], "Grad_out": [enc.name]},
                {"m": m, "ratio": ratio,
                 "rampup_begin_step": rampup_begin})
            compressed.append((p, block.var(enc.name)))
        opt_ops = self.inner_opt.apply_gradients(compressed)
        loss.block.program._bump()
        return opt_ops, params_grads


class FP16AllReduceMetaOptimizer(MetaOptimizerBase):
    """Cast grads to fp16/bf16 around the allreduce
    (reference fp16_allreduce_optimizer.py)."""

    def _can_apply(self):
        return self.user_strategy.fp16_allreduce

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        ops, params_grads = self.inner_opt.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        loss.block.program._fp16_allreduce = True
        return ops, params_grads


class LocalSGDMetaOptimizer(MetaOptimizerBase):
    """Periodic param averaging instead of per-step allreduce
    (reference localsgd_optimizer.py)."""

    can_be_last = True

    def _can_apply(self):
        return self.user_strategy.localsgd

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        import jax

        if jax.process_count() == 1 and self._nranks() > 1:
            # single-process SPMD keeps params replicated across the mesh,
            # so per-replica divergence between averages cannot exist —
            # localsgd would silently train on shard 0's data only.
            raise NotImplementedError(
                "strategy.localsgd needs per-replica parameter state: run "
                "one process per host (paddle_tpu.distributed.launch) so "
                "each process holds its own params, or use "
                "strategy.gradient_merge for step-K synchronization in the "
                "single-process SPMD runtime")
        ops, params_grads = self.inner_opt.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        cfg = self.user_strategy.localsgd_configs
        prog = loss.block.program
        prog._localsgd = LocalSGD(jax.process_count(), k_steps=cfg["k_steps"])
        prog._localsgd.build_average_program(prog)
        return ops, params_grads


_OPTIMIZER_OP_TYPES = {
    "sgd", "momentum", "adam", "adamw", "adamax", "adagrad", "adadelta",
    "rmsprop", "ftrl", "lamb", "lars_momentum", "dgc_momentum", "dpsgd",
}

# param-shaped accumulator input slots per optimizer op (reference
# operators/optimizers/*_op.cc input declarations); Beta*Pow and loss-
# scale scalars are [1]-shaped and deliberately absent
_OPTIMIZER_ACC_SLOTS = {
    "sgd": (),
    "momentum": ("Velocity",),
    "lars_momentum": ("Velocity",),
    "dgc_momentum": ("Velocity",),
    "adam": ("Moment1", "Moment2"),
    "adamw": ("Moment1", "Moment2"),
    "lamb": ("Moment1", "Moment2"),
    "adamax": ("Moment", "InfNorm"),
    "adagrad": ("Moment",),
    "adadelta": ("AvgSquaredGrad", "AvgSquaredUpdate"),
    "rmsprop": ("MeanSquare", "MeanGrad", "Moment"),
    "ftrl": ("SquaredAccumulator", "LinearAccumulator"),
}


class ShardingMetaOptimizer(MetaOptimizerBase):
    """ZeRO-1 optimizer-state sharding (reference
    fleet/meta_optimizers/sharding_optimizer.py:33).

    TPU-native form: instead of assigning whole params to ranks and
    broadcasting (reference _split_program/_add_broadcast_allreduce), every
    param/grad with dim0 divisible by the dp degree is sliced evenly —
    each rank updates its 1/nranks shard with its shard of the (allreduced)
    grad, optimizer accumulators live sharded over the mesh (in/out specs
    P('dp') in the SPMD executor), and `c_allgather` re-assembles the
    updated param for the next forward.  Memory for optimizer state drops
    ~linearly with the dp degree."""

    can_be_last = True  # replaces the plain DP transpile

    def _can_apply(self):
        return self.user_strategy.sharding and self._nranks() > 1

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        ops, params_grads = self.inner_opt.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        prog = loss.block.program
        n = self._nranks()
        sharded_params = self._sharded_param_set(prog, params_grads, n)
        if not sharded_params:
            raise ValueError(
                "strategy.sharding=True but no parameter has dim0 divisible "
                f"by the dp degree {n}; sharding would be a no-op")
        # gradient_merge composition: the merge chain moves into shard
        # space — acc/merged ride the grad SHARD (c_reducescatter output)
        # and join the sharded optimizer state, so merge-accumulator
        # memory also drops by the dp degree
        gm_map = self._collect_gm_map(prog.global_block)
        self._transpile_grads(prog, params_grads, sharded_params,
                              loss.name + GRAD_SUFFIX, gm_map=gm_map)
        self._shard_optimizer_ops(prog, n, sharded_params, gm_map=gm_map)
        return ops, params_grads

    @staticmethod
    def _collect_gm_map(block):
        """Reconstruct {orig grad -> {acc, merged}} from the __gm_grad__
        attrs the merge optimizer stamps on its accumulate ops (attrs,
        not a python side channel, so a clone/proto round-trip between
        the two meta-optimizers cannot lose the linkage)."""
        out = {}
        for i, op in enumerate(block.ops):
            g = op.attr("__gm_grad__", None)
            if not g:
                continue
            acc = op.inputs["X"][0]
            for op2 in block.ops[i + 1:]:
                if op2.type == "elementwise_mul" \
                        and op2.inputs.get("X") == [acc] \
                        and op2.outputs.get("Out") != [acc]:
                    out[g] = {"acc": acc,
                              "merged": op2.outputs["Out"][0]}
                    break
        return out

    def _sharded_param_set(self, prog, params_grads, nranks):
        block = prog.global_block
        out = set()
        for p, _ in params_grads:
            pvar = block._find_var_recursive(
                p.name if hasattr(p, "name") else p)
            if pvar is not None and pvar.shape \
                    and int(pvar.shape[0]) % nranks == 0:
                out.add(pvar.name)
        return out

    def _transpile_grads(self, prog, params_grads, sharded_params,
                         loss_grad_name, gm_map=None):
        """ZeRO-1 grad comm: `c_reducescatter` for sharded params (each
        rank receives only its grad shard — half the volume of
        allreduce+slice), plain `c_allreduce_sum` for params left
        replicated.  Loss-grad 1/nranks scaling as in GradAllReduce."""
        from ...framework import dtypes
        from ...framework.passes import DP_LOSS_SCALE_ATTR
        from ...framework.program import Operator

        n = self._nranks()
        fp16 = bool(getattr(prog, "_fp16_allreduce", False))
        block = prog.global_block
        grad_to_param = {}
        for p, g in params_grads:
            grad_to_param[g.name if hasattr(g, "name") else g] = (
                p.name if hasattr(p, "name") else p)

        last_writer = _last_writer_map(block.ops)
        new_ops = []
        for i, op in enumerate(block.ops):
            new_ops.append(op)
            if loss_grad_name in op.output_arg_names() \
                    and op.type == "fill_constant":
                new_ops.append(Operator(
                    block, "scale", {"X": [loss_grad_name]},
                    {"Out": [loss_grad_name]},
                    {"scale": 1.0 / n, "bias": 0.0,
                     "bias_after_scale": True,
                     DP_LOSS_SCALE_ATTR: True}))
            for g in op.output_arg_names():
                pname = grad_to_param.get(g)
                if pname is None or last_writer.get(g) != i:
                    continue
                comm_in = g
                if fp16:
                    new_ops.append(Operator(
                        block, "cast", {"X": [g]}, {"Out": [g]},
                        {"out_dtype": dtypes.to_enum("bfloat16")}))
                if pname in sharded_params:
                    gvar = block._find_var_recursive(g)
                    g_shard = g + "@SHARD"
                    if not block.has_var(g_shard):
                        shape = list(gvar.shape) if gvar is not None else []
                        if shape:
                            shape[0] = int(shape[0]) // n
                        block.create_var(name=g_shard, shape=shape,
                                         dtype=(gvar.dtype if gvar else
                                                "float32"),
                                         stop_gradient=True)
                    new_ops.append(Operator(
                        block, "c_reducescatter", {"X": [comm_in]},
                        {"Out": [g_shard]}, {"ring_id": 0}))
                    if fp16:
                        new_ops.append(Operator(
                            block, "cast", {"X": [g_shard]},
                            {"Out": [g_shard]},
                            {"out_dtype": dtypes.to_enum("float32")}))
                else:
                    new_ops.append(Operator(
                        block, "c_allreduce_sum", {"X": [comm_in]},
                        {"Out": [g]}, {"ring_id": 0}))
                    if fp16:
                        new_ops.append(Operator(
                            block, "cast", {"X": [g]}, {"Out": [g]},
                            {"out_dtype": dtypes.to_enum("float32")}))
        # gradient_merge composition: the merge accumulation must consume
        # the grad SHARD (its X/Out accumulator joins the sharded state),
        # not the pre-scatter full grad
        if gm_map:
            for op in new_ops:
                if op.type != "elementwise_add":
                    continue
                y = op.inputs.get("Y", [])
                if len(y) == 1 and y[0] in gm_map \
                        and grad_to_param.get(y[0]) in sharded_params \
                        and op.inputs.get("X") == [gm_map[y[0]]["acc"]]:
                    op.inputs["Y"] = [y[0] + "@SHARD"]
        block.ops[:] = new_ops
        prog._bump()

    def _shard_optimizer_ops(self, prog, nranks, sharded_params,
                             gm_map=None):
        from ...framework.program import Operator

        block = prog.global_block
        # merged-grad name -> its accumulator (gradient_merge composition)
        merged_to_acc = {info["merged"]: info["acc"]
                         for info in (gm_map or {}).values()}
        new_ops = []
        for op in block.ops:
            if op.type not in _OPTIMIZER_OP_TYPES:
                new_ops.append(op)
                continue
            pnames = op.inputs.get("Param", [])
            gnames = op.inputs.get("Grad", [])
            if len(pnames) != 1 or len(gnames) != 1 \
                    or pnames[0] not in sharded_params:
                new_ops.append(op)
                continue
            pname, gname = pnames[0], gnames[0]
            pvar = block._find_var_recursive(pname)
            shard_shape = [int(pvar.shape[0]) // nranks] + [
                int(s) for s in pvar.shape[1:]]
            p_shard = pname + "@SHARD"
            # a merged grad already lives in shard space (the merge chain
            # consumed the reducescatter output); plain grads rewire to
            # the @SHARD var the scatter produced
            g_shard = gname if gname in merged_to_acc \
                else gname + "@SHARD"
            if not block.has_var(p_shard):
                block.create_var(name=p_shard, shape=shard_shape,
                                 dtype=pvar.dtype, stop_gradient=True)
            new_ops.append(Operator(block, "c_shard_slice",
                                    {"X": [pname]}, {"Out": [p_shard]}, {}))
            # rewire the update to run on the local shard; accumulators
            # (same shape as the param, read & written) become sharded
            # state, recorded ON the op so the program is self-describing
            # (survives clone/proto round-trips, unlike a python attr)
            outs_set = set(op.output_arg_names())
            sharded_accs = []
            acc_slots = _OPTIMIZER_ACC_SLOTS.get(op.type)
            for slot, names in list(op.inputs.items()):
                if slot == "Param":
                    op.inputs[slot] = [p_shard]
                elif slot == "Grad":
                    op.inputs[slot] = [g_shard]
                elif acc_slots is not None:
                    # exact accumulator identification by slot name —
                    # a same-shaped persistable input in a non-acc slot
                    # (e.g. a MasterParam) must NOT be sharded blindly
                    if slot in acc_slots:
                        sharded_accs.extend(names)
                else:
                    # unknown optimizer type: fall back to the shape
                    # heuristic (persistable, param-shaped, read+written)
                    for nm in names:
                        v = block._find_var_recursive(nm)
                        if (v is not None and v.persistable
                                and tuple(v.shape) == tuple(pvar.shape)
                                and nm in outs_set):
                            sharded_accs.append(nm)
            for slot, names in list(op.outputs.items()):
                op.outputs[slot] = [p_shard if nm == pname else nm
                                    for nm in names]
            if gname in merged_to_acc:
                # the merge accumulator carries shard-space values:
                # record it so the executor gives it a P('dp') spec —
                # merge memory drops by the dp degree like other state
                sharded_accs.append(merged_to_acc[gname])
            op.attrs["__sharded_accumulators__"] = sharded_accs
            new_ops.append(op)
            new_ops.append(Operator(block, "c_allgather",
                                    {"X": [p_shard]}, {"Out": [pname]},
                                    {"ring_id": 0}))
        block.ops[:] = new_ops
        prog._bump()


class PipelineMetaOptimizer(MetaOptimizerBase):
    """GPipe pipeline parallelism (reference
    fleet/meta_optimizers/pipeline_optimizer.py:90 + fluid
    PipelineOptimizer optimizer.py:3695).  Wraps the inner optimizer with
    paddle_tpu.optimizer.PipelineOptimizer; the program must be built with
    device_guard('stage:N') annotations and executed over a mesh with a
    'pp' axis (distributed/pipeline.py)."""

    can_be_last = True  # graph-level: replaces the plain DP transpile

    def _can_apply(self):
        return self.user_strategy.pipeline

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ..parallel_env import get_mesh
        from ...optimizer.pipeline_opt import PipelineOptimizer

        mesh = get_mesh()
        if mesh is not None and "pp" not in mesh.axis_names:
            raise ValueError(
                "strategy.pipeline needs a mesh with a 'pp' axis; build it "
                "with init_parallel_env(axis_names=('pp',)) or "
                "set_mesh(Mesh(devs, ('pp',)))")
        cfg = self.user_strategy.pipeline_configs
        k = int(cfg.get("micro_batch", 1))
        return PipelineOptimizer(self.inner_opt, num_microbatches=k).minimize(
            loss, startup_program, parameter_list, no_grad_set)


class TensorParallelMetaOptimizer(MetaOptimizerBase):
    """Tensor-parallel (Megatron-style intra-layer) sharding over a
    named dp×mp mesh — reference
    fleet/meta_optimizers/tensor_parallel_optimizer.py role, GSPMD-
    native form.

    Outermost wrapper (NOT a can_be_last graph-level optimizer): it
    composes with whichever graph-level chain applied — the plain DP
    transpile, ZeRO-1 sharding, fused allreduce, AMP, recompute — by
    stamping the partition-rule contract onto the program's optimizer
    ops (``TP_RULES_ATTR``/``TP_DEGREE_ATTR``, surviving clone/proto
    round-trips and re-keying every executor cache via the
    fingerprint).  The executor-side ``ShardingPropagationPass`` turns
    the rules into a :class:`~paddle_tpu.framework.passes.TPShardingPlan`
    and the Executor compiles through jit + ``NamedSharding``.

    The one program rewrite done HERE: the dp transpile's 1/nranks
    loss-grad scale op (marked ``DP_LOSS_SCALE_ATTR``) is removed —
    under GSPMD the traced loss is the global-batch mean, so its
    gradient is already exact; keeping the scale would shrink every
    gradient by the dp degree."""

    def _can_apply(self):
        return self.user_strategy.tensor_parallel

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ...framework.passes import (DEFAULT_MEGATRON_RULES,
                                         DP_LOSS_SCALE_ATTR, TP_DEGREE_ATTR,
                                         TP_RULES_ATTR, decode_spec,
                                         encode_spec)
        from ..parallel_env import get_mesh

        strat = self.user_strategy
        if strat.localsgd:
            # pipeline now COMPOSES (the dp×mp×pp mesh: pipeline stages
            # partition the block, tp rules shard within each stage's
            # blocks — distributed/pipeline.py manual Megatron path);
            # localsgd remains genuinely unsupported: its periodic
            # host-side parameter averaging runs between executor calls
            # and has no mp-sharded form here
            raise NotImplementedError(
                "strategy.tensor_parallel does not compose with "
                "strategy.localsgd yet: both re-own program "
                "execution; unset one")
        mesh = get_mesh()
        if mesh is not None and "mp" not in mesh.axis_names:
            raise ValueError(
                "strategy.tensor_parallel needs a mesh with an 'mp' "
                "axis; build it with init_parallel_env(mesh_shape="
                "(dp, mp), axis_names=('dp', 'mp'))")
        if strat.pipeline and mesh is not None \
                and "pp" not in mesh.axis_names:
            raise ValueError(
                "strategy.tensor_parallel + strategy.pipeline needs a "
                "mesh with BOTH 'mp' and 'pp' axes; build it with "
                "init_parallel_env(mesh_shape=(dp, mp, pp), "
                "axis_names=('dp', 'mp', 'pp'))")

        ops, params_grads = self.inner_opt.minimize(
            loss, startup_program, parameter_list, no_grad_set)

        cfg = strat.tensor_parallel_configs or {}
        # proto default is 1 ("unset"): 0 in the stamp means "use the
        # mesh's mp axis size"; an explicit degree >= 2 is VALIDATED
        # against the mesh at dispatch time
        degree = int(cfg.get("tensor_parallel_degree") or 0)
        if degree <= 1:
            degree = 0
        rules = cfg.get("partition_rules") or DEFAULT_MEGATRON_RULES
        encoded = []
        for pat, spec in rules:
            if not isinstance(spec, str):
                spec = encode_spec(spec)
            decode_spec(spec)  # validate early: bad specs fail HERE
            encoded.append(f"{pat}\t{spec}")

        prog = loss.block.program
        block = prog.global_block
        block.ops[:] = [op for op in block.ops
                        if not op.attr(DP_LOSS_SCALE_ATTR)]
        stamped = False
        for op in block.ops:
            if op.type in _OPTIMIZER_OP_TYPES:
                op.attrs[TP_RULES_ATTR] = list(encoded)
                op.attrs[TP_DEGREE_ATTR] = degree
                stamped = True
        if not stamped:
            raise ValueError(
                "strategy.tensor_parallel found no optimizer ops to "
                "stamp its partition rules on; minimize() must build "
                "the training program first")
        prog._bump()
        return ops, params_grads


class ExpertParallelMetaOptimizer(MetaOptimizerBase):
    """Expert parallelism (mixture-of-experts) over a named mesh with an
    'ep' axis — the reference's incubate MoE distributed layer, GSPMD-
    native form.

    Outermost wrapper like TensorParallelMetaOptimizer: it composes
    with whichever graph-level chain applied by stamping
    ``EP_DEGREE_ATTR`` onto the program's optimizer ops; the executor-
    side ``ShardingPropagationPass`` then seeds ``P('ep', ...)`` on
    every moe_ffn op's stacked expert weights, stamps the all-to-all
    anchors, and refuses ep-sharded consumers outside the routed-FFN
    family.  The dp loss-grad scale op is removed here for the same
    reason as the tp meta-optimizer: under GSPMD the traced loss is the
    global-batch mean already."""

    def _can_apply(self):
        return self.user_strategy.expert_parallel

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ...framework.passes import DP_LOSS_SCALE_ATTR, EP_DEGREE_ATTR
        from ..parallel_env import get_mesh

        strat = self.user_strategy
        if strat.localsgd:
            raise NotImplementedError(
                "strategy.expert_parallel does not compose with "
                "strategy.localsgd yet: localsgd's host-side parameter "
                "averaging has no ep-sharded form here; unset one")
        mesh = get_mesh()
        if mesh is not None and "ep" not in mesh.axis_names:
            raise ValueError(
                "strategy.expert_parallel needs a mesh with an 'ep' "
                "axis; build it with init_parallel_env(mesh_shape="
                "(dp, ep), axis_names=('dp', 'ep')) or FLAGS_ep_degree")
        if strat.pipeline and mesh is not None \
                and "pp" not in mesh.axis_names:
            raise ValueError(
                "strategy.expert_parallel + strategy.pipeline needs a "
                "mesh with BOTH 'ep' and 'pp' axes; build it with "
                "init_parallel_env(mesh_shape=(dp, ep, pp), "
                "axis_names=('dp', 'ep', 'pp'))")

        ops, params_grads = self.inner_opt.minimize(
            loss, startup_program, parameter_list, no_grad_set)

        cfg = strat.expert_parallel_configs or {}
        # 0 in the stamp means "use the mesh's ep axis size"; an
        # explicit degree >= 2 is VALIDATED against the mesh at
        # dispatch time (ShardingPropagationPass)
        degree = int(cfg.get("expert_parallel_degree") or 0)
        if degree <= 1:
            degree = 0

        prog = loss.block.program
        block = prog.global_block
        if not any(op.type == "moe_ffn" for op in block.ops):
            raise ValueError(
                "strategy.expert_parallel found no moe_ffn ops to "
                "shard; build the model with layers.moe_ffn(...) or "
                "unset the strategy")
        block.ops[:] = [op for op in block.ops
                        if not op.attr(DP_LOSS_SCALE_ATTR)]
        stamped = False
        for op in block.ops:
            if op.type in _OPTIMIZER_OP_TYPES:
                op.attrs[EP_DEGREE_ATTR] = degree
                stamped = True
        if not stamped:
            raise ValueError(
                "strategy.expert_parallel found no optimizer ops to "
                "stamp its degree on; minimize() must build the "
                "training program first")
        prog._bump()
        return ops, params_grads


class GraphExecutionMetaOptimizer(MetaOptimizerBase):
    """The default collective DP transpile (reference
    graph_execution_optimizer.py:92 + transpiler/collective.py:244)."""

    can_be_last = True

    def _can_apply(self):
        return self._nranks() > 1

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        ops, params_grads = self.inner_opt.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        prog = loss.block.program
        strat = self.user_strategy
        GradAllReduce(
            self._nranks(),
            fuse_all_reduce=bool(strat.fuse_all_reduce_ops)
            if strat is not None else True,
            fuse_grad_size_in_MB=(strat.fuse_grad_size_in_MB or 32)
            if strat is not None else 32,
            fp16=bool(getattr(prog, "_fp16_allreduce", False)),
        ).transpile(prog, params_grads,
                    loss_grad_name=loss.name + GRAD_SUFFIX)
        return ops, params_grads


META_OPTIMIZERS = [
    LarsMetaOptimizer,
    LambMetaOptimizer,
    # GradientMerge innermost of the wrappers: it drives backward/apply
    # directly, so program-rewrite metas (AMP) must run outside it
    GradientMergeMetaOptimizer,
    DGCMetaOptimizer,
    AMPMetaOptimizer,
    RecomputeMetaOptimizer,
    FP16AllReduceMetaOptimizer,
    LocalSGDMetaOptimizer,
    PipelineMetaOptimizer,  # graph-level; wins over plain DP when set
    ShardingMetaOptimizer,  # graph-level; wins over plain DP when set
    GraphExecutionMetaOptimizer,
    # OUTERMOST (wraps the graph-level winner): stamps the tensor-
    # parallel rule contract after the dp/ZeRO transpile ran, so it
    # composes with fused-allreduce, AMP, recompute, and ZeRO chains
    TensorParallelMetaOptimizer,
    # expert parallelism rides the same GSPMD substrate and the same
    # outermost position (stamps after every transpile, composes with
    # tp — 'ep' and 'mp' shard disjoint weight families)
    ExpertParallelMetaOptimizer,
]

# strategy flags with no implementation yet: refuse loudly rather than
# silently training without the requested behavior (the reference raises
# when a meta-optimizer is unavailable too)
_UNSUPPORTED_FLAGS = ("a_sync", "elastic", "sequence_parallel")


def compile_strategy(loss, role_maker, inner_opt, strategy):
    """Longest-compatible-chain ordering (reference strategy_compiler.py:89):
    each applicable meta-optimizer wraps the previous; graph-level ones
    (can_be_last) are mutually exclusive — the first applicable wins."""
    for flag in _UNSUPPORTED_FLAGS:
        if getattr(strategy, flag, False):
            raise NotImplementedError(
                f"DistributedStrategy.{flag} is not implemented in the TPU "
                f"runtime; unset it (silently ignoring it would train "
                f"without the requested behavior)")
    chain = inner_opt
    last_used = False
    applied = set()
    for cls in META_OPTIMIZERS:
        mo = cls(chain)
        mo._set_basic_info(loss, role_maker, inner_opt, strategy)
        if not mo._can_apply():
            continue
        if mo.can_be_last:
            if last_used:
                continue
            last_used = True
        applied.add(cls)
        chain = mo
    # graph-level strategies must not be silently dropped when another
    # graph-level meta-optimizer won the can_be_last slot
    graph_level = {"localsgd": LocalSGDMetaOptimizer,
                   "pipeline": PipelineMetaOptimizer,
                   "sharding": ShardingMetaOptimizer}
    winner = next((name for name, cls in graph_level.items()
                   if cls in applied), None)
    for name, cls in graph_level.items():
        if getattr(strategy, name, False) and cls not in applied:
            if winner is not None:
                reason = (f"it conflicts with strategy.{winner} (both are "
                          f"graph-level; only one can transpile the program)")
            else:
                reason = "it needs a data-parallel degree > 1"
            raise ValueError(
                f"strategy.{name}=True could not be applied: {reason}")
    return chain
