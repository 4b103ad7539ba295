"""A served model whose layers are of two kinds: softmax attention with
grouped-query heads and an output gate, and gated-delta-rule linear
attention (a decay a channel, a short causal convolution) that keeps a
fixed-size recurrent state a request instead of keys; every layer's
feed-forward is a mixture of experts of which this chip HOLDS A SHARE
(``ops/moe_ops.py`` ``moe_share_*``) beside a shared expert.  The
architecture is Solar-Open2-250B's; the equations are in the reference's
docstring (``benchmark/reference/hybrid_moe_lm.py``, a copy in
``tests/``), which this file is tested against and shares no code with.

It sits behind ``DecodeEngine`` on the contract in that class's
docstring, like ``transformer_lm.py``: ``forward(weights, tokens,
positions, cache, attend)``.  What it declares beyond the reference
model's attributes: ``layer_kinds`` (``"attention"`` or ``"recurrent"`` a
layer), ``num_kv_heads``, ``recurrent_state`` (one slot's state of one
recurrent layer, ``{name: (shape, dtype)}``), ``tallies`` (the counters
``forward`` adds to, by name).  What it asks of ``attend``
beyond the call: ``attend.recur`` runs a recurrent layer's one-token
update over the rows' state, ``attend.live`` masks dead and padding rows
out of the routing, ``attend.tally`` and ``attend.record`` take the
routing counts and the chosen expert ids.

The recurrent layers' rule has ONE definition in two forms, chosen by
``ops/pallas_kda_update.py`` ``kda_rule`` from the state's static shape
alone: a float32 state of whole lane tiles (the served widths) goes
through the kernel there, which holds a block of heads' states in VMEM,
reads and writes each once, in place; anything else (every toy width)
through the XLA lines of ``_kda_rule_xla``, which are also the tests'
oracle.  In either form the one-token update takes ``live`` and owns
the dead rows, so the engine's step passes over no slab itself (with
the kernel, nothing but the kernel does).  Where the kernel takes the
state a whole PROMPT does not go through the token rule at all: the
prefill gets a chunk function, ``_kda_chunk``, the rule's chunk (WY)
form on the matrix unit (``ops/pallas_kda_chunk.py``: ``PREFILL_CHUNK``
tokens at a time, the decay ratios a channel from differences of summed
LOG decays that are never above 0, every product float32 at
``highest``), a group of chunks a call over a state that stays in VMEM
between them; the group is ``prefill_chunks_per_call``'s, from the
bucket's rows and the model's widths alone.  It is the ONE prompt form:
a shape the kernels do not take (every toy width) hands the engine no
chunk function and its prompt goes token by token through the XLA lines.

Precision as served: weights (and K/V pages) in ``dtype`` (bfloat16),
every matmul accumulating in float32; the residual stream, norms, router
scores, softmax, the gates and THE RECURRENT STATE in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import numpy as np

from ..ops import moe_ops
from ..ops import pallas_kda_chunk as kda_chunk
from ..ops import pallas_kda_update as kda

KDA_SCOPE = "kda_update"
# tokens of one chunk of the rule's WY form, what the whole-prompt
# prefill's scan counts as a step
PREFILL_CHUNK = kda_chunk.CHUNK
# float32 bytes of ONE call's temporaries the group of chunks is cut to
# (the state passes through HBM once a call, the call's vectors are
# formed at once): 256 tokens a call at 32 heads, 128 at 64.  PERF.md
# section 6 (PR 58) has what groups of 1 to a whole bucket read on the
# chip: they differ by 2-3 %, a call of a whole 256-row bucket at 64
# heads by 18 %
GROUP_BYTES = 32 << 20


def step_tallies(model, rows):
    """Of a routed model's declared ``tallies``, those a joint step of
    ``rows`` rows reads back: the hit form's only where that step takes
    the form (``moe_ops.hit_rule``: the step's static shape and the
    model's own routing), so a step that keeps the dense form is the
    program it was.  The routed models' ``step_tallies`` method."""
    if moe_ops.hit_rule(rows, len(model.held_experts), model.expert_dim,
                        model.d_model, model.top_k, model.num_experts):
        return model.tallies
    return tuple(n for n in model.tallies if n not in moe_ops.HIT_TALLIES)


class KDAMixer:
    """The channel-decay delta-rule mixer of a model with ``lin_heads``
    heads of ``lin_head_dim``, a convolution of ``conv_kernel`` taps,
    low-rank gates of ``gate_rank`` and ``rms_eps``: its weights, one
    slot's state, the mixer's residual term and the rule's two forms.
    ``beta_scale`` is the range of the rule's step: ``(0, 2)`` where the
    published config allows negative eigenvalues, ``(0, 1)`` else.
    ``HybridMoELM``'s, and ``linear_latent_lm.py``'s."""

    beta_scale = 2.0

    def kda_state(self):
        """One slot's state of ONE recurrent layer: the delta rule's
        matrix a head, and the K-1 positions the convolution looks back
        on, oldest first, side by side in one lane-dense row."""
        c = self.lin_heads * self.lin_head_dim
        return {
            "s": ((self.lin_heads, self.lin_head_dim, self.lin_head_dim),
                  np.float32),
            "tail": (((self.conv_kernel - 1) * 3 * c,), np.float32)}

    def kda_weights(self, dense, keys, ones):
        """A recurrent layer's mixer weights; the decay's
        ``A_log``/``dt_bias`` as the gated linear-attention families set
        them (rates 1..16, steps 1e-3..1e-1: decays 0.2..0.999)."""
        import jax
        import jax.numpy as jnp

        dm, r = self.d_model, self.gate_rank
        c = self.lin_heads * self.lin_head_dim
        rate = jax.random.uniform(next(keys), (self.lin_heads,),
                                  jnp.float32, 1.0, 16.0)
        step = jnp.exp(jax.random.uniform(
            next(keys), (c,), jnp.float32,
            math.log(1e-3), math.log(1e-1)))
        return dict(
            kda_wqkv=dense((dm, 3 * c)),
            kda_conv=dense((self.conv_kernel, 3 * c),
                           1.0 / math.sqrt(self.conv_kernel),
                           jnp.float32),
            kda_a_log=jnp.log(rate),
            # softplus^-1(step)
            kda_dt_bias=step + jnp.log(-jnp.expm1(-step)),
            kda_wa_down=dense((dm, r)), kda_wa_up=dense((r, c)),
            kda_wbeta=dense((dm, self.lin_heads)),
            kda_wo_down=dense((dm, r)), kda_wo_up=dense((r, c)),
            kda_onorm=ones(self.lin_head_dim),
            kda_wout=dense((c, dm)))

    def kda_mixer(self, l, lw, h, cache, attend):
        """Recurrent layer ``l``'s residual term of the normed rows
        ``h`` -> (``y``, cache)."""
        import jax
        import jax.numpy as jnp

        rows = {"u": _mm(h, lw["kda_wqkv"]),
                "gate": _mm(_mm(h, lw["kda_wa_down"]),
                            lw["kda_wa_up"]),
                "beta": _mm(h, lw["kda_wbeta"])}
        o, cache = self._recur(l, lw, rows, cache, attend)
        o = o * jax.lax.rsqrt(jnp.mean(
            o * o, -1, keepdims=True) + self.rms_eps) \
            * lw["kda_onorm"]
        return _mm(o.reshape(*h.shape[:-1], -1) * jax.nn.sigmoid(_mm(
            _mm(h, lw["kda_wo_down"]), lw["kda_wo_up"])),
            lw["kda_wout"]), cache

    def _recur(self, l, lw, rows, cache, attend):
        """Recurrent layer ``l`` over the rows' projections -> (``o``,
        cache).  Where the kernels take the state's shape a whole-prompt
        prefill runs ``prefill_chunks_per_call`` of the rule's chunks a
        call through ``_kda_chunk`` (the chunk form; the engine's loop
        holds all of it), and a step, which runs the token rule through
        ``_kda_token`` whatever else is handed over, counts the rows it
        updated."""
        import jax.numpy as jnp

        token = functools.partial(self._kda_token, lw,
                                  interpret=attend.interpret)
        group = self.prefill_chunks_per_call(rows["u"].shape[0])
        if not group:
            return attend.recur(l, token, rows, cache)
        if not attend.prompt:
            attend.tally("kda_kernel_rows",
                         jnp.sum(attend.live, dtype=jnp.int32))
        return attend.recur(
            l, token, rows, cache, chunk=group * PREFILL_CHUNK,
            chunk_fn=functools.partial(self._kda_chunk, lw,
                                       interpret=attend.interpret),
            chunks_per_call=group)

    def prefill_chunks_per_call(self, rows):
        """Chunks of the rule's WY form (``PREFILL_CHUNK`` tokens) ONE
        call of ``_kda_chunk`` takes of a prompt bucket of ``rows``
        rows, where the kernels take the state: all of them, up to what
        ``GROUP_BYTES`` of the call's float32 temporaries allow (a
        token's: the convolved rows, q, k and v, the log decay and the
        output, eight rows of all heads' lanes); else 0, no chunk form.
        A function of the bucket and the model's widths alone."""
        shape, dtype = self.recurrent_state["s"]
        if not kda.kda_rule(*shape, dtype):
            return 0
        a_chunk = 4 * PREFILL_CHUNK * 8 * self.lin_heads * self.lin_head_dim
        return max(1, min(-(-int(rows) // PREFILL_CHUNK),
                          GROUP_BYTES // a_chunk))

    def _kda_vectors(self, lw, conv, gate, beta, log_decay=False):
        """What the rule takes of ``N`` tokens, from their convolved
        rows ``conv [N, 3C]`` and the ``gate [N, C]`` and ``beta [N,
        heads]`` projections -> (q, k, v, decay ``[N, heads, dk]``, beta
        ``[N, heads]``): q and k at unit length a head, q scaled by
        ``dk^-1/2``; the decay a channel in (0, 1), or with
        ``log_decay`` its logarithm as it is formed (what the chunk
        form sums: never above 0, and there where the factor itself
        has underflowed); beta in (0, ``beta_scale``)."""
        import jax
        import jax.numpy as jnp

        nh, dk = self.lin_heads, self.lin_head_dim
        q, k, v = jnp.moveaxis(jax.nn.silu(conv).reshape(
            -1, 3, nh, dk), 1, 0)
        q = q * jax.lax.rsqrt(
            jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(dk)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        decay = -jnp.exp(lw["kda_a_log"])[:, None] * jax.nn.softplus(
            gate + lw["kda_dt_bias"]).reshape(-1, nh, dk)
        if not log_decay:
            decay = jnp.exp(decay)
        return q, k, v, decay, self.beta_scale * jax.nn.sigmoid(beta)

    def _kda_token(self, lw, rows, state, live=None, interpret=False):
        """One token a row through a recurrent layer: ``rows`` the
        token's projections (``u [R, 3C]`` before the convolution,
        ``gate [R, C]``, ``beta [R, heads]``), ``state`` the rows' state
        BEFORE it (``s [R, heads, dk, dv]``, ``tail [R, (K-1)*3C]``) ->
        (``o [R, heads, dv]``, the state after it).  All float32.

        It takes ``live`` (bool ``[R]``; None: every row) and OWNS the
        dead rows: a row that is not live comes back with the state it
        had, so the caller passes over no slab to mask it again.  Where
        ``kda_rule`` takes the state's shape the matrices go through the
        kernel, read once and written once where they lie (a dead row's
        blocks written back as read); else through ``_kda_rule_xla``
        (read twice and written once, and once more for the mask)."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope(KDA_SCOPE):
            c3 = rows["u"].shape[-1]
            window = jnp.concatenate([state["tail"], rows["u"]], axis=1)
            conv = sum(window[:, j * c3:(j + 1) * c3] * lw["kda_conv"][j]
                       for j in range(self.conv_kernel))
            q, k, v, decay, beta = self._kda_vectors(
                lw, conv, rows["gate"], rows["beta"])
            s0, tail = state["s"], window[:, c3:]
            if kda.kda_rule(*s0.shape[1:], s0.dtype):
                n = jnp.ones(s0.shape[:1], jnp.int32) if live is None \
                    else live.astype(jnp.int32)
                o, s = kda.kda_update(
                    q[:, None], k[:, None], decay[:, None], v[:, None],
                    beta[:, None], s0, n, interpret=interpret)
                o = o[:, 0]
            else:
                o, s = _kda_rule_xla(q, k, v, decay, beta, s0)
                if live is not None:
                    s = jnp.where(live[:, None, None, None], s, s0)
            if live is not None:
                tail = jnp.where(live[:, None], tail, state["tail"])
        return o, {"s": s, "tail": tail}

    def _kda_chunk(self, lw, rows, n_real, state, interpret=False):
        """A GROUP of whole ``PREFILL_CHUNK``-token chunks, consecutive
        tokens of ONE request, through a recurrent layer in one call of
        the chunk kernel (``ops/pallas_kda_chunk.py``: the rule's WY
        form on the matrix unit, a chunk at a time over a state that
        stays in VMEM; the step's kernel is the token rule itself and
        this calls it nowhere): ``rows`` their projections (``u [N,
        3C]``, ``gate``, ``beta``), of which the first ``n_real`` are
        the request's (the kernel masks the rest, ``beta = 0`` and ``g =
        0``, and skips chunks of nothing else: padding touches neither
        the matrices nor the tail), ``state`` the request's before them
        (leading dimension 1) -> (``o [N, heads, dv]``, zero past
        ``n_real``; the state after token ``n_real - 1``).  The
        convolution, the norms and beta are the token form's, over all
        the call's rows at once; the decay is handed over as its
        LOGARITHM, as ``_kda_vectors`` forms it, and every product of
        the form has float32 operands at ``highest``."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope(KDA_SCOPE):
            c, c3 = rows["u"].shape
            window = jnp.concatenate(
                [state["tail"].reshape(self.conv_kernel - 1, c3),
                 rows["u"]])
            conv = sum(window[j:j + c] * lw["kda_conv"][j]
                       for j in range(self.conv_kernel))
            q, k, v, log_decay, beta = self._kda_vectors(
                lw, conv, rows["gate"], rows["beta"], log_decay=True)
            o, s = kda_chunk.kda_chunk(
                q[None], k[None], log_decay[None], v[None], beta[None],
                state["s"], jnp.reshape(n_real, (1,)), interpret=interpret)
            tail = jax.lax.dynamic_slice_in_dim(
                window, n_real, self.conv_kernel - 1)
        return o[0], {"s": s, "tail": tail.reshape(1, -1)}


class HybridMoELM(KDAMixer):
    """Sized by constructor arguments; ``layer_kinds`` is the pattern
    (Solar-Open2: one ``"attention"`` then three ``"recurrent"`` a
    period).  ``held_experts`` are the routed-expert ids this chip holds
    of ``num_experts``; the router keeps its full width."""

    def __init__(self, vocab_size: int, d_model: int,
                 layer_kinds: Sequence[str], num_heads: int,
                 num_kv_heads: int, head_dim: int, lin_heads: int,
                 lin_head_dim: int, conv_kernel: int, gate_rank: int,
                 num_experts: int, top_k: int,
                 held_experts: Sequence[int], expert_dim: int,
                 shared_dim: int, rms_eps: float = 1e-5,
                 dtype="bfloat16", max_seq_len: int = 1 << 20):
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.layer_kinds = tuple(layer_kinds)
        bad = set(self.layer_kinds) - {"attention", "recurrent"}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer_kinds holds {sorted(bad) or 'nothing'}")
        self.num_layers = len(self.layer_kinds)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        self.head_dim = int(head_dim)
        self.lin_heads, self.lin_head_dim = int(lin_heads), int(lin_head_dim)
        self.conv_kernel, self.gate_rank = int(conv_kernel), int(gate_rank)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held_experts = held_ids(held_experts, self.num_experts)
        self.expert_dim, self.shared_dim = int(expert_dim), int(shared_dim)
        self.rms_eps = float(rms_eps)
        self.dtype = str(dtype)
        self.max_seq_len = int(max_seq_len)     # no positional table
        # the counters forward adds to through attend.tally: a joint
        # step's, and those only a whole-prompt prefill reads back
        # ``kda_kernel_rows``: live rows x recurrent layers whose state a
        # step's kernel calls updated (0 where the XLA form serves)
        # ``HIT_TALLIES``: read back by a step that takes the hit form
        # (``step_tallies``), counted and dropped anywhere else
        self.tallies = ("moe_local_assignments", "moe_experts_hit",
                        "kda_kernel_rows") + moe_ops.HIT_TALLIES
        self.prefill_tallies = moe_ops.GROUPED_TALLIES
        self.recurrent_state = self.kda_state()

    step_tallies = step_tallies

    # -- weights ------------------------------------------------------------
    def init_weights(self, key):
        """Seeded weights at variance-preserving scales
        (``kda_weights`` has the decay's)."""
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        dm, v = self.d_model, self.vocab_size
        hq = self.num_heads * self.head_dim
        hkv = self.num_kv_heads * self.head_dim
        e, f = self.num_experts, self.expert_dim
        nf = len(self.held_experts) * f
        keys = iter(jax.random.split(key, 4 + 24 * self.num_layers))

        dense = dense_from(keys, dt)
        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w = {"tok_emb": dense((v, dm), 1.0), "lm_head": dense((dm, v)),
             "norm_f": ones(dm), "layers": []}
        for kind in self.layer_kinds:
            lw = {"norm1": ones(dm), "norm2": ones(dm)}
            if kind == "attention":
                lw.update(wq=dense((dm, hq)), wk=dense((dm, hkv)),
                          wv=dense((dm, hkv)), wg=dense((dm, hq)),
                          wo=dense((hq, dm)))
            else:
                lw.update(self.kda_weights(dense, keys, ones))
            lw.update(
                moe_router=dense((dm, e), dtype=jnp.float32),
                moe_router_bias=jnp.zeros((e,), jnp.float32),
                moe_w_gate=dense((dm, nf)), moe_w_up=dense((dm, nf)),
                moe_w_down=dense((nf, dm), 1.0 / math.sqrt(f)),
                shared_w_gate=dense((dm, self.shared_dim)),
                shared_w_up=dense((dm, self.shared_dim)),
                shared_w_down=dense((self.shared_dim, dm)))
            w["layers"].append(lw)
        return w

    # -- the block ------------------------------------------------------------
    def forward(self, weights, tokens, positions, cache, attend):
        """Logits for ``tokens`` (``[S]`` one token a slot, ``[T]`` one
        prompt) -> ``(logits [..., V], cache)``; ``positions`` are not
        read (no positional term).  See the module header for what
        ``attend`` carries."""
        import jax
        import jax.numpy as jnp

        w = weights
        x = w["tok_emb"][tokens].astype(jnp.float32)
        lead = x.shape[:-1]
        for l, kind in enumerate(self.layer_kinds):
            lw = w["layers"][l]
            h = self._rms(x, lw["norm1"])
            if kind == "attention":
                q = _mm(h, lw["wq"]).reshape(*lead, self.num_heads,
                                             self.head_dim)
                k = _mm(h, lw["wk"]).reshape(*lead, self.num_kv_heads,
                                             self.head_dim)
                v = _mm(h, lw["wv"]).reshape(*lead, self.num_kv_heads,
                                             self.head_dim)
                ctx, cache = attend(l, q, k, v, cache)
                y = _mm(ctx.reshape(*lead, -1).astype(jnp.float32)
                        * jax.nn.sigmoid(_mm(h, lw["wg"])), lw["wo"])
            else:
                y, cache = self.kda_mixer(l, lw, h, cache, attend)
            x = x + y
            h = self._rms(x, lw["norm2"])
            local = route_share(h, lw, attend, self.top_k,
                                self.held_experts)
            with jax.named_scope("moe_shared"):
                shared = _mm(jax.nn.silu(_mm(h, lw["shared_w_gate"]))
                             * _mm(h, lw["shared_w_up"]),
                             lw["shared_w_down"])
            x = x + share_ffn(self, h, lw, local, attend) + shared
        return _mm(self._rms(x, w["norm_f"]), w["lm_head"]), cache

    def _rms(self, x, g):
        return rms_norm(x, g, self.rms_eps)


def _kda_rule_xla(q, k, v, decay, beta, s):
    """The gated delta rule, one token a row, as XLA fusions: ``q``,
    ``k``, ``v``, ``decay [R, heads, d]``, ``beta [R, heads]``, ``s [R,
    heads, dk, dv]`` before the token -> (``o [R, heads, dv]``, ``s``
    after it).  ``S'^T k`` and ``S'^T q`` come out of one pass over the
    decayed state, and ``o = S'^T q + (k.q) b (v - S'^T k)`` is ``S_t^T
    q`` without a third.  The form of every shape the kernel does not
    take, and what the kernel is tested against."""
    import jax.numpy as jnp

    s = decay[..., None] * s                           # Diag(a) S
    ks = jnp.sum(k[..., None] * s, axis=-2)            # S'^T k
    qs = jnp.sum(q[..., None] * s, axis=-2)            # S'^T q
    delta = beta[..., None] * (v - ks)
    s = s + k[..., None] * delta[..., None, :]
    return qs + jnp.sum(q * k, -1, keepdims=True) * delta, s


def held_ids(held_experts, num_experts):
    """``held_experts`` as a tuple of distinct ids below
    ``num_experts``, or a ``ValueError``."""
    held = tuple(int(e) for e in held_experts)
    if not held or min(held) < 0 or max(held) >= num_experts \
            or len(set(held)) != len(held):
        raise ValueError(
            f"held_experts must be distinct ids below {num_experts}")
    return held


def dense_from(keys, dt):
    """``dense(shape, scale=None, dtype=dt)``: a seeded normal matrix at
    a variance-preserving scale (``shape[0] ** -0.5`` where none is
    given), a key of ``keys`` a call."""
    import jax
    import jax.numpy as jnp

    def dense(shape, scale=None, dtype=dt):
        scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    return dense


def _mm(a, w):
    """``a @ w`` at the weight's dtype in, float32 out."""
    import jax.numpy as jnp

    return jnp.matmul(a.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def share_ffn(model, h, lw, local, attend):
    """The held experts' part of the routed result for rows ``h`` under
    ``route_share``'s ``local``, in the form the call's shape and the
    model's published routing choose (``moe_ops.moe_share_ffn``)."""
    return moe_ops.moe_share_ffn(
        h, local, lw["moe_w_gate"], lw["moe_w_up"], lw["moe_w_down"],
        tally=attend.tally, interpret=attend.interpret,
        top_k=model.top_k, num_experts=model.num_experts)


def route_share(h, lw, attend, top_k, held_experts):
    """Rows ``h`` routed over all of the layer's experts: the weights
    of the held ones a row (``moe_ops.moe_share_route``'s ``local``),
    with the counts tallied and the chosen ids recorded through
    ``attend``.  Shared with ``window_moe_lm.py``."""
    ids, _, local = moe_ops.moe_share_route(
        h, lw["moe_router"], lw["moe_router_bias"], top_k=top_k,
        held_ids=held_experts, live=attend.live)
    assigned, hit = moe_ops.moe_share_counts(local)
    attend.tally("moe_local_assignments", assigned)
    attend.tally("moe_experts_hit", hit)
    attend.record("moe_topk", ids)
    return local
