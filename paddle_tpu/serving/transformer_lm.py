"""The served model.  The one place in ``paddle_tpu/`` that says what a
layer is: the engine (``serving/decode.py``) calls ``forward`` and
supplies ``attend``; its contract is in ``DecodeEngine``'s docstring.
"""
from __future__ import annotations

import math
from typing import Optional

from ..monitor import stat_add


class TransformerLM:
    """A decoder-only transformer sized by constructor args — the
    engine's reference model (bench, tests, demos).  What the engine
    needs of ANY model is the contract in ``DecodeEngine``'s docstring;
    here that is ``forward``, built of pieces that are all
    row-independent (layer norm, QKV/out projections, MLP), so prefill
    and decode run the SAME block on their rows and cached decode stays
    bitwise-comparable to a full recompute."""

    def __init__(self, vocab_size: int, d_model: int = 64,
                 num_layers: int = 2, num_heads: int = 2,
                 ffn_dim: Optional[int] = None, max_seq_len: int = 256,
                 moe_experts: int = 0, moe_top_k: int = 2,
                 moe_capacity_factor: float = 0.0, moe_mesh=None):
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        if d_model % num_heads:
            raise ValueError("d_model must divide by num_heads")
        self.head_dim = self.d_model // self.num_heads
        self.ffn_dim = int(ffn_dim) if ffn_dim else 4 * self.d_model
        self.max_seq_len = int(max_seq_len)
        # MoE FFN (ops/moe_ops.moe_ffn_ref): moe_experts > 0 replaces
        # the dense MLP with a top-k routed expert FFN.  The default
        # capacity factor 0.0 means DROPLESS (cap = E/K * S*K/E = S):
        # with no drops the routed output is row-independent
        # MATHEMATICALLY, so cached decode agrees with a prefill
        # recompute to float tolerance — but not bitwise: the dispatch
        # buffer's capacity tracks the row count, and XLA's reduction
        # strategy is shape-dependent (~1 ulp).  A finite factor
        # additionally reintroduces batch-dependent drops (fine for
        # training, wrong for the serving oracle).
        # ``moe_mesh`` with an 'ep' axis turns on expert-parallel
        # decode: the stacked expert weights live P('ep', ...) and the
        # dispatch/combine all-to-alls materialize around the FFN.
        self.moe_experts = int(moe_experts)
        self.moe_top_k = int(moe_top_k)
        self.moe_capacity_factor = float(moe_capacity_factor)
        self.moe_mesh = moe_mesh
        if self.moe_experts:
            if self.moe_top_k > self.moe_experts:
                raise ValueError(
                    f"moe_top_k={moe_top_k} exceeds "
                    f"moe_experts={moe_experts}")
            if moe_mesh is not None and "ep" not in getattr(
                    moe_mesh, "axis_names", ()):
                raise ValueError(
                    "moe_mesh needs an 'ep' axis for expert-parallel "
                    "decode; build one with init_parallel_env("
                    "mesh_shape=(dp, ep), axis_names=('dp', 'ep'))")

    def init_weights(self, key):
        import jax
        import jax.numpy as jnp

        dm, f, v = self.d_model, self.ffn_dim, self.vocab_size
        n_per_layer = 7 if self.moe_experts else 6
        keys = jax.random.split(key, 3 + self.num_layers * n_per_layer)

        def dense(k, shape, scale=None):
            scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
            return (jax.random.normal(k, shape) * scale).astype(jnp.float32)

        w = {
            "tok_emb": dense(keys[0], (v, dm), 0.02),
            "pos_emb": dense(keys[1], (self.max_seq_len, dm), 0.02),
            "lm_head": dense(keys[2], (dm, v)),
            "lnf_g": jnp.ones((dm,), jnp.float32),
            "lnf_b": jnp.zeros((dm,), jnp.float32),
            "layers": [],
        }
        for i in range(self.num_layers):
            k = keys[3 + i * n_per_layer: 3 + (i + 1) * n_per_layer]
            lw = {
                "ln1_g": jnp.ones((dm,), jnp.float32),
                "ln1_b": jnp.zeros((dm,), jnp.float32),
                "wq": dense(k[0], (dm, dm)),
                "wk": dense(k[1], (dm, dm)),
                "wv": dense(k[2], (dm, dm)),
                "wo": dense(k[3], (dm, dm)),
                "ln2_g": jnp.ones((dm,), jnp.float32),
                "ln2_b": jnp.zeros((dm,), jnp.float32),
            }
            if self.moe_experts:
                e = self.moe_experts
                lw["gate"] = dense(k[4], (dm, e), 0.02)
                lw["moe_w1"] = dense(k[5], (e, dm, f))
                lw["moe_b1"] = jnp.zeros((e, f), jnp.float32)
                lw["moe_w2"] = dense(k[6], (e, f, dm),
                                     1.0 / math.sqrt(f))
                lw["moe_b2"] = jnp.zeros((e, dm), jnp.float32)
            else:
                lw["w1"] = dense(k[4], (dm, f))
                lw["w2"] = dense(k[5], (f, dm))
            w["layers"].append(lw)
        return w

    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` at ``positions`` (any leading shape:
        ``[S]``, ``[T]``, ``[S, R]``) -> ``(logits [..., V], cache)``.
        The caller's ``attend(layer, q, k, v, cache) -> (ctx, cache)``
        takes a layer's ``[..., H, D]`` rows, keeps K/V where it will
        and returns the context in q's shape; ``cache`` is its pytree,
        only threaded through."""
        w = weights
        x = w["tok_emb"][tokens] + w["pos_emb"][positions]  # [..., Dm]
        for l in range(self.num_layers):
            lw = w["layers"][l]
            h = self._ln(x, lw["ln1_g"], lw["ln1_b"])
            q, k, v = self._qkv(lw, h)                      # [..., H, D]
            ctx, cache = attend(l, q, k, v, cache)
            x = x + ctx.reshape(*x.shape) @ lw["wo"]
            x = x + self._mlp(
                lw, self._ln(x, lw["ln2_g"], lw["ln2_b"]))
        return self._ln(x, w["lnf_g"], w["lnf_b"]) @ w["lm_head"], cache

    # -- the block's pure per-row pieces ----------------------------------
    @staticmethod
    def _ln(x, g, b):
        import jax.numpy as jnp

        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    def _qkv(self, lw, h):
        n, d = self.num_heads, self.head_dim
        q = (h @ lw["wq"]).reshape(*h.shape[:-1], n, d)
        k = (h @ lw["wk"]).reshape(*h.shape[:-1], n, d)
        v = (h @ lw["wv"]).reshape(*h.shape[:-1], n, d)
        return q, k, v

    def _mlp(self, lw, h):
        import jax

        if self.moe_experts:
            return self._moe_mlp(lw, h)
        return jax.nn.gelu(h @ lw["w1"]) @ lw["w2"]

    def _moe_mlp(self, lw, h):
        """Routed expert FFN, dropless by default (see __init__).
        Quantized expert carriers (``quantize_moe_weights``) dequantize
        per expert at the einsum's doorstep; a ``moe_mesh`` with an
        'ep' axis adds the GSPMD constraints that make the dispatch and
        combine all-to-alls real."""
        from ..ops.moe_ops import _dequant_stacked, moe_ffn_ref

        if "moe_w1_q" in lw:
            w1 = _dequant_stacked(lw["moe_w1_q"], lw["moe_w1_scale"])
            w2 = _dequant_stacked(lw["moe_w2_q"], lw["moe_w2_scale"])
        else:
            w1, w2 = lw["moe_w1"], lw["moe_w2"]
        cf = self.moe_capacity_factor or (
            self.moe_experts / self.moe_top_k)
        out, _aux, _load, _chunked = moe_ffn_ref(
            h, lw["gate"], w1, lw["moe_b1"], w2, lw["moe_b2"],
            num_experts=self.moe_experts, top_k=self.moe_top_k,
            capacity_factor=cf, mesh=self.moe_mesh,
            ep=self.moe_mesh is not None)
        return out.astype(h.dtype)



def quantize_moe_weights(weights, mode: str = "int8"):
    """Post-training quantization of a TransformerLM weight dict's
    stacked expert tensors — the serving twin of the
    PostTrainingWeightQuantPass moe_ffn branch (slim/quantization.py):
    every layer's ``moe_w1``/``moe_w2`` becomes an int8 (or fp8)
    carrier plus a per-expert ``[E, out]`` scale
    (ops/quant_ops.quantize_weight_stacked), which ``_moe_mlp``
    dequantizes at the expert einsum's doorstep.  Gate, biases, and
    everything dense stay full precision (they're a rounding error of
    the byte footprint).  Returns a NEW dict; the original is
    untouched (it stays the full-precision oracle)."""
    from ..ops.quant_ops import quantize_weight_stacked

    out = dict(weights)
    layers = []
    n_quantized = 0
    for lw in weights["layers"]:
        lw = dict(lw)
        if "moe_w1" in lw:
            for nm in ("moe_w1", "moe_w2"):
                q, s = quantize_weight_stacked(lw.pop(nm), 2, mode)
                lw[nm + "_q"] = q
                lw[nm + "_scale"] = s
                n_quantized += 1
        layers.append(lw)
    if not n_quantized:
        raise ValueError(
            "quantize_moe_weights found no stacked expert weights; "
            "build the model with moe_experts > 0")
    out["layers"] = layers
    stat_add("serving_moe_weights_quantized", n_quantized)
    return out


def shard_moe_weights(weights, mesh):
    """Place a TransformerLM weight dict's stacked expert tensors (raw
    or quantized carriers+scales alike) ``P('ep', ...)`` on ``mesh`` so
    each chip holds only its 1/ep slice of the experts — the serving
    counterpart of the ShardingPropagationPass 'ep' seed.  Everything
    else replicates.  Returns a NEW dict of device-resident arrays."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if "ep" not in getattr(mesh, "axis_names", ()):
        raise ValueError(
            "shard_moe_weights needs a mesh with an 'ep' axis; build "
            "one with init_parallel_env(mesh_shape=(dp, ep), "
            "axis_names=('dp', 'ep'))")
    ep = int(mesh.shape["ep"])

    def put(val, spec):
        return jax.device_put(val, NamedSharding(mesh, spec))

    rep = PartitionSpec()
    out = {k: put(v, rep) for k, v in weights.items() if k != "layers"}
    layers = []
    for lw in weights["layers"]:
        placed = {}
        for nm, val in lw.items():
            stacked = nm.startswith("moe_w") and val.ndim >= 2 \
                or nm in ("moe_b1", "moe_b2")
            if stacked and int(val.shape[0]) % ep == 0:
                placed[nm] = put(val, PartitionSpec(
                    "ep", *([None] * (val.ndim - 1))))
            else:
                placed[nm] = put(val, rep)
        layers.append(placed)
    out["layers"] = layers
    return out
