"""Plain forward of a decoder with a PARALLEL block (one LayerNorm feeds
attention and feed-forward, one add joins them), sliding-window layers
with a rotary term beside position-free global layers, a mixture of
experts with averaged shared experts, and a tied head: the yardstick for
``correct`` of the cells that serve ``paddle_tpu.serving.parallel_moe_lm``.

The architecture is Command A+'s language model
(``CohereLabs/command-a-plus-05-2026`` ``config.json``, ``model_type:
cohere2_moe``), written out from the weights dictionary in ``jax.numpy``
float32 at ``highest`` matmul precision over the WHOLE sequence: no
cache, no pages, no ring, no kernel, no batching, and none of the
model's own methods.

The equations.  Layer ``l``, input ``x [T, Dm]`` float32, ``kind_l`` from
``layer_types`` (``sliding_attention`` -> ``"window"``,
``full_attention`` -> ``"attention"``)::

    h      = (x - mean(x)) / sqrt(var(x) + eps) * g_l         # LayerNorm, no bias
    q,k,v  = h Wq [T,H,D],  h Wk [T,Hkv,D],  h Wv [T,Hkv,D]   # no biases, no QK-norm
    window : q,k <- rotate(q,k): lanes (2j, 2j+1) of every head turn by
             pos * theta^(-2j/D), j = 0..D/2-1 (rope_gptj, rotary_pct 1);
             row i attends j <= i with i - j < window
    global : no positional term;  row i attends every j <= i
    ctx    = softmax_f32(q k^T / sqrt(D)) v,  query head i reads K/V head i // (H/Hkv)
    a      = ctx Wo
    r      = sigmoid(h Wr) in R^E;  ids = top-k(r);  w = r[ids] / sum r[ids]
    routed = sum_k w_k E_{ids_k}(h),   E(h) = (silu(h Wg) * h Wu) Wd
    shared = 1/n * sum_{s=1..n} Sh_s(h)                       # same form, averaged
    x      = x + a + routed + shared                          # ONE add: the parallel block
    logits = logit_scale * LayerNorm_f(x) Emb^T               # Emb the input embedding

There is no leading dense layer (``first_k_dense_replace`` 0), no router
correction bias, no sink, no value scale, no head matrix.

The share.  ``dims["held"]`` lists the routed-expert ids this chip holds
(one chip's share of an expert-parallel group); the weights hold those
experts only, expert ``held[j]`` in columns ``j*F:(j+1)*F`` of
``moe_w_gate``/``moe_w_up`` and rows ``j*F:(j+1)*F`` of ``moe_w_down``.
``routed`` then runs over the chosen experts that are held: what the
absent experts would add is left out, here as in the program, and that
partial result goes on to the next layer.  The shared experts lie side
by side the same way in ``shared_w_*`` and every chip holds all of them.

Assumptions the published config is silent on (the configuration file
lists them): ``intermediate_size`` is one expert's width (routed and
shared alike); ``shared_expert_combination_strategy: "average"`` is the
mean of the shared experts' outputs, added to the routed sum;
``sliding_window`` counts the token itself; no router correction bias.

``routing`` (optional, ``[T, L, k]`` expert ids the SERVED model chose):
the layers then follow the ids instead of their own top-k, after
measuring how far each chosen id lies below the reference's own k-th
largest score (returned as ``gap``: 0 where they agree, a near-tie flip
is a few 1e-3 of a score); weights and everything else are computed
here.  Weights may be bfloat16: each is upcast where it is used, the
experts one at a time, the K/V heads one group at a time and the query
rows ``dims["row_block"]`` (512) at a time, each block against the keys
its rows can see, so that 4,400 positions at the published widths fit
beside a served copy of the model.
"""
import math


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _layer_norm(x, g, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(g)


def _rotary_pairs(x, theta):
    """x [T, heads, D] at positions 0..T-1: lanes (2j, 2j+1) turn by
    ``p * theta^(-2j / D)``: ``(a, b) -> (a cos - b sin, b cos + a sin)``.
    The partner of each lane comes from a signed permutation matrix
    (``x @ swap`` puts ``-b`` on lane 2j and ``a`` on lane 2j+1), so no
    array has a trailing dimension of 2 (which pads 64-fold on the chip)."""
    import jax.numpy as jnp

    t, _, d = x.shape
    j = jnp.arange(d) // 2
    p = jnp.arange(t, dtype=jnp.float32)[:, None, None]
    angle = p / theta ** (2.0 * j.astype(jnp.float32) / d)   # [T, 1, D]
    lane = jnp.arange(d)
    partner = lane + 1 - 2 * (lane % 2)                      # 2j <-> 2j+1
    sign = jnp.where(lane % 2 == 0, -1.0, 1.0)
    swap = jnp.zeros((d, d), jnp.float32).at[partner, lane].set(sign)
    return x * jnp.cos(angle) + (x @ swap) * jnp.sin(angle)


def attention(lw, h, dims, kind):
    """``a`` of the equations for the normed rows h [T, Dm]."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    nh, hkv, d = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    q = (h @ _f32(lw["wq"])).reshape(t, nh, d)
    k = (h @ _f32(lw["wk"])).reshape(t, hkv, d)
    v = (h @ _f32(lw["wv"])).reshape(t, hkv, d)
    if kind == "window":
        q = _rotary_pairs(q, dims["rope_theta"])
        k = _rotary_pairs(k, dims["rope_theta"])
    q = jnp.moveaxis(q.reshape(t, hkv, nh // hkv, d), 1, 0)  # [hkv,T,G,D]
    k, v = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)      # [hkv,T,D]
    step = int(dims.get("row_block", 512))
    out = []
    for r0 in range(0, t, step):
        r1 = min(r0 + step, t)
        c0 = max(r0 - dims["window"] + 1, 0) if kind == "window" else 0
        i = jnp.arange(r0, r1)[:, None]
        j = jnp.arange(c0, r1)[None, :]
        seen = j <= i
        if kind == "window":
            seen = seen & (i - j < dims["window"])

        def group(args, seen=seen):
            """The query heads of one K/V head, rows r0..r1."""
            qg, kg, vg = args               # [R,G,D] [C,D] [C,D]
            s = jnp.einsum("igd,jd->gij", qg, kg) / math.sqrt(d)
            s = jnp.where(seen[None], s, -jnp.inf)
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = e / jnp.sum(e, axis=-1, keepdims=True)
            return jnp.einsum("gij,jd->igd", p, vg)

        ctx = jax.lax.map(group, (q[:, r0:r1], k[:, c0:r1], v[:, c0:r1]))
        out.append(jnp.moveaxis(ctx, 0, 1).reshape(r1 - r0, nh * d))
    return jnp.concatenate(out, axis=0) @ _f32(lw["wo"])


def routed(lw, h, dims, ids=None, held=None):
    """(``routed`` of the equations for h [T, Dm], gap [T]).  ``ids``
    [T, k]: follow these experts (``gap`` says how far below the
    reference's own k-th score the worst of them lies); ``held``
    overrides ``dims["held"]`` as the ids whose weights ``lw`` holds."""
    import jax
    import jax.numpy as jnp

    held = dims["held"] if held is None else held
    f, top_k = dims["expert_dim"], dims["top_k"]
    scores = jax.nn.sigmoid(h @ _f32(lw["moe_router"]))      # [T, E]
    kth = jax.lax.top_k(scores, top_k)[0][:, -1]
    if ids is None:
        ids = jax.lax.top_k(scores, top_k)[1]
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    gap = jnp.max(kth[:, None] - chosen, axis=1)
    w = chosen / jnp.sum(chosen, axis=1, keepdims=True)

    def expert(j, y):
        mine = jnp.sum(jnp.where(
            ids == jnp.asarray(held, jnp.int32)[j], w, 0.0), axis=1)
        return y + mine[:, None] * _swiglu(
            h, lw["moe_w_gate"], lw["moe_w_up"], lw["moe_w_down"], j, f)

    return jax.lax.fori_loop(0, len(held), expert, jnp.zeros_like(h)), gap


def _swiglu(h, w_gate, w_up, w_down, j, f):
    """Expert j of width f out of matrices that hold several side by
    side, upcast alone."""
    import jax

    cols = lambda m: _f32(jax.lax.dynamic_slice_in_dim(  # noqa: E731
        m, j * f, f, axis=1))
    act = jax.nn.silu(h @ cols(w_gate)) * (h @ cols(w_up))
    return act @ _f32(jax.lax.dynamic_slice_in_dim(w_down, j * f, f, axis=0))


def shared(lw, h, dims):
    """``shared`` of the equations: the MEAN of the shared experts."""
    import jax
    import jax.numpy as jnp

    n, f = dims["shared_experts"], dims["shared_dim"]
    total = jax.lax.fori_loop(
        0, n, lambda s, y: y + _swiglu(
            h, lw["shared_w_gate"], lw["shared_w_up"], lw["shared_w_down"],
            s, f), jnp.zeros_like(h))
    return total / n


def block(lw, x, dims, kind, ids=None, held=None):
    """One layer's residual update of the whole sequence x [T, Dm] ->
    (x, gap [T]): everything reads the one normed ``h``, one add."""
    import jax

    with jax.default_matmul_precision("highest"):
        h = _layer_norm(x, lw["norm"], dims["eps"])
        moe, gap = routed(lw, h, dims, ids, held)
        return x + attention(lw, h, dims, kind) + moe \
            + shared(lw, h, dims), gap


def head(w, x, dims):
    import jax

    with jax.default_matmul_precision("highest"):
        return dims["logit_scale"] * (
            _layer_norm(x, w["norm_f"], dims["eps"]) @ _f32(w["tok_emb"]).T)


def forward_logits(w, tokens, dims, routing=None):
    """``tokens`` [T] int32 -> (logits [T, vocab], gap [T, L]), L the
    layers.  Every position is real: nothing here is causal but the
    attention, so rows past a sequence's end only cost time."""
    import jax.numpy as jnp

    x = _f32(w["tok_emb"][tokens])
    gaps = []
    for l, (kind, lw) in enumerate(zip(dims["kinds"], w["layers"])):
        x, gap = block(lw, x, dims, kind,
                       None if routing is None else routing[:, l])
        gaps.append(gap)
    return head(w, x, dims), jnp.stack(gaps, axis=1)
