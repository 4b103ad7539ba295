"""The four stateful mixers a served model composes, as mixins: what a
layer keeps of a request lives with the engine (``attend``), what the
layer computes of it is here, once, for every model that has such a
layer.

``KDAMixer``: the channel-decay gated delta rule (``"recurrent"``
layers: a fixed-size float32 state a slot in slabs, no keys).  The rule
has ONE definition in two forms, chosen by ``ops/pallas_kda_update.py``
``kda_rule`` from the state's static shape alone: a float32 state of
whole lane tiles (the served widths) goes through the kernel there,
anything else (every toy width) through the XLA lines of
``_kda_rule_xla``, which are also the tests' oracle; where the kernel
takes the state a whole PROMPT runs the rule's chunk (WY) form on the
matrix unit (``ops/pallas_kda_chunk.py``), a group of chunks a call.
``HybridMoELM``'s and ``LinearLatentLM``'s.

``LatentMixer``: latent attention (``"attention"`` layers whose cached
row is ``[c | rot(k_r)]``, no V pool) in its two forms, expanded over a
whole prompt and absorbed in the step.  ``LatentMoELM``'s and
``LinearLatentLM``'s.

``SSMMixer``: the selective state-space (Mamba-1) layer
(``"recurrent"`` layers: a float32 state of ``[d_state, d_inner]`` a
slot in which every entry decays by itself, and the convolution's last
inputs; no heads, no keys).  ONE rule in two forms, chosen by
``ops/pallas_ssm.py`` ``ssm_rule`` from the state's static shape: the
step's kernel over every live slot's state in place, and a prompt's scan
with the state in fast memory, a group of ``SCAN_CHUNK``-token tiles a
call; anything else (toy widths) runs ``ssm_token_xla`` a token, which
is also the tests' oracle.  ``MambaLM``'s.

``IndexedMixer``: grouped-query attention over the positions a learned
INDEXER selects (``"attention"`` layers that cache, beside K and V, one
small index key a position in a third pool).  The indexer is here ONCE
in two forms that choose the same positions: the step's (every live
key of a slot scored, the ``index_topk`` best gathered and attended) and
a prompt's (blocks of query rows: a mask a pair).  The operations are
``ops/indexed_attention.py``'s.  ``IndexedMoELM``'s.

A mixin reads the model's declared widths off ``self`` and imports no
model file: ``blocks.py`` alone of ``serving/``
(``tests/test_tooling.py`` holds the arrows).
"""
from __future__ import annotations

import functools
import math

import numpy as np

from ..ops import indexed_attention as ixa
from ..ops import pallas_kda_chunk as kda_chunk
from ..ops import pallas_kda_update as kda
from ..ops import pallas_ssm as ssm
from .blocks import (ROPE_SCOPE, _mm, half_split_angles, half_split_rotate,
                     rms_norm)

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

# the state-space layer's operations, the step's and a prompt's apart
SSM_STEP_SCOPE = "ssm_update"           # the state kernel of the step
SSM_SCAN_SCOPE = "ssm_scan"             # the state kernel of a prompt
SSM_CONV_SCOPE = "ssm_conv"             # the taps, the bias, SiLU, the tail
SSM_IN_SCOPE = "ssm_in_proj"            # W_in: u | z
SSM_X_SCOPE = "ssm_x_proj"              # W_x, the three norms, W_dt
SSM_OUT_SCOPE = "ssm_out_proj"          # the gate and W_out
# tokens of one tile of the prompt's scan, what the whole-prompt prefill
# counts as a scan step; tokens ONE call of the scan takes at most (its
# vectors, nine float32 rows of all channels a token, are formed at once)
SCAN_CHUNK = ssm.SCAN_TILE
SCAN_CALL_TOKENS = 1024

# the indexed attention's operations, the step's and a prompt's apart
INDEX_PROJ_SCOPE = "index_proj"                     # W_Iq, W_Ik, W_Iw
INDEX_SCORE_SCOPE = "index_scores"                  # I[t, s], a step's
INDEX_SELECT_SCOPE = "index_select"                 # the topk of it
SPARSE_ATTN_SCOPE = "sparse_attention"              # over the selected rows
INDEX_SCORE_PROMPT_SCOPE = "index_scores_prompt"
INDEX_SELECT_PROMPT_SCOPE = "index_select_prompt"
SPARSE_ATTN_PROMPT_SCOPE = "sparse_attention_prompt"
# what a request that records its logits keeps of every layer's selection:
# a step's row the positions (-1 beyond the live ones), a prompt's row a
# bit a key (``ixa.pack_bits``; all zeros for a row under ``index_topk``,
# which attends every live position)
INDEX_RECORD = "index_selected"

Q_PROJ_SCOPE = "latent_q_proj"          # q_a, its norm, q_b
KV_PROJ_SCOPE = "latent_kv_proj"        # kv_a and the latent's norm
ABSORB_Q_SCOPE = "latent_absorb_q"      # q_nope W_UK: into the row's space
ABSORB_V_SCOPE = "latent_absorb_v"      # ctx_lat W_UV: out of it
EXPAND_SCOPE = "latent_expand"          # a prompt's K and V from its rows


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


class SSMMixer:
    """The selective state-space mixer of a model with ``d_inner``
    channels, ``d_state`` state rows a channel, a convolution of
    ``d_conv`` taps, a step projection of ``dt_rank`` and ``rms_eps``:
    its weights, one slot's state, the mixer's residual term and the
    rule's two forms.  ``MambaLM``'s."""

    def ssm_state(self):
        """One slot's state of ONE recurrent layer, laid out for the
        chip: ``ssm`` the ``[d_state, d_inner]`` state (the channels on
        the lanes: ``[d_inner, d_state]`` would pad 16 lanes to 128),
        ``conv`` the ``d_conv - 1`` rows the convolution looks back on,
        oldest first, side by side in one lane-dense row (three rows of
        their own would pad to eight sublanes)."""
        return {"ssm": ((self.d_state, self.d_inner), np.float32),
                "conv": (((self.d_conv - 1) * self.d_inner,), np.float32)}

    def ssm_weights(self, dense, keys, ones):
        """A recurrent layer's mixer weights.  The diagonal's as the
        state-space families set them: ``A_log[n, c] = log(n + 1)``
        (rates 1..d_state, S4D-real), ``b_dt`` the inverse softplus of
        steps log-uniform in 1e-3..1e-1, ``D`` ones; ``A_log`` lies as
        the state does, ``[d_state, d_inner]``."""
        import jax
        import jax.numpy as jnp

        dm, d, n, r = self.d_model, self.d_inner, self.d_state, self.dt_rank
        step = jnp.exp(jax.random.uniform(
            next(keys), (d,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dict(
            ssm_w_in=dense((dm, 2 * d)),
            ssm_conv=dense((self.d_conv, d), 1.0 / math.sqrt(self.d_conv),
                           jnp.float32),
            ssm_conv_b=dense((d,), 0.1, jnp.float32),
            ssm_w_x=dense((d, r + 2 * n)),
            ssm_dt_norm=ones(r), ssm_b_norm=ones(n), ssm_c_norm=ones(n),
            ssm_w_dt=dense((r, d)),
            # softplus^-1(step)
            ssm_dt_b=step + jnp.log(-jnp.expm1(-step)),
            ssm_a_log=jnp.broadcast_to(jnp.log(jnp.arange(
                1, n + 1, dtype=jnp.float32))[:, None], (n, d)),
            ssm_d=ones(d),
            ssm_w_out=dense((d, dm)))

    def ssm_mixer(self, l, lw, h, cache, attend):
        """Recurrent layer ``l``'s residual term of the normed rows
        ``h`` -> (``y``, cache).  Where the kernels take the state's
        shape a whole-prompt prefill runs ``prefill_chunks_per_call``
        tiles of the scan a call (``_ssm_chunk``), and a step, which
        runs the token rule through ``_ssm_token`` whatever else is
        handed over, counts the rows it updated."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope(SSM_IN_SCOPE):
            uz = _mm(h, lw["ssm_w_in"])
        u, z = uz[..., :self.d_inner], uz[..., self.d_inner:]
        group = self.prefill_chunks_per_call(u.shape[0])
        if group and not attend.prompt:
            attend.tally("ssm_kernel_rows",
                         jnp.sum(attend.live, dtype=jnp.int32))
        o, cache = attend.recur(
            l, functools.partial(self._ssm_token, lw,
                                 interpret=attend.interpret),
            {"u": u}, cache, chunk=group * SCAN_CHUNK,
            chunk_fn=functools.partial(
                self._ssm_chunk, lw, interpret=attend.interpret)
            if group else None, chunks_per_call=max(group, 1))
        with jax.named_scope(SSM_OUT_SCOPE):
            return _mm(o * jax.nn.silu(z), lw["ssm_w_out"]), cache

    def prefill_chunks_per_call(self, rows):
        """Tiles of the scan (``SCAN_CHUNK`` tokens) ONE call of
        ``_ssm_chunk`` takes of a prompt bucket of ``rows`` rows, where
        the kernels take the state: all of them, up to
        ``SCAN_CALL_TOKENS``; else 0, no chunk form.  A function of the
        bucket and the model's widths alone."""
        shape, dtype = self.recurrent_state["ssm"]
        if not ssm.ssm_rule(*shape, dtype):
            return 0
        return max(1, min(-(-int(rows) // SCAN_CHUNK),
                          SCAN_CALL_TOKENS // SCAN_CHUNK))

    def _ssm_vectors(self, lw, u):
        """What the rule takes of ``N`` tokens beside their convolved,
        activated rows ``u [N, d_inner]`` -> (dt ``[N, d_inner]``, b, c
        ``[N, d_state]``, a ``[d_state, d_inner]``): the step, ``b`` and
        ``c`` each through an RMSNorm of its own, the step's bias inside
        the softplus, ``a`` below zero."""
        import jax
        import jax.numpy as jnp

        r, n = self.dt_rank, self.d_state
        with jax.named_scope(SSM_X_SCOPE):
            x = _mm(u, lw["ssm_w_x"])
            dt = rms_norm(x[..., :r], lw["ssm_dt_norm"], self.rms_eps)
            b = rms_norm(x[..., r:r + n], lw["ssm_b_norm"], self.rms_eps)
            c = rms_norm(x[..., r + n:], lw["ssm_c_norm"], self.rms_eps)
            dt = jax.nn.softplus(_mm(dt, lw["ssm_w_dt"]) + lw["ssm_dt_b"])
        return dt, b, c, -jnp.exp(lw["ssm_a_log"])

    def _ssm_token(self, lw, rows, state, live=None, interpret=False):
        """One token a row through a recurrent layer: ``rows`` the
        token's in-projection (``u [R, d_inner]`` before the
        convolution), ``state`` the rows' state BEFORE it (``ssm [R,
        d_state, d_inner]``, ``conv [R, (K-1) d_inner]``) -> (``y + D u
        [R, d_inner]``, the state after it).  All float32.

        It takes ``live`` (bool ``[R]``; None: every row) and OWNS the
        dead rows: a row that is not live comes back with the state it
        had.  Where ``ssm_rule`` takes the state's shape both arrays go
        through kernels, each read once and written once where it lies
        (a dead row's blocks written back as read); else through
        ``ssm_conv_xla`` and ``ssm_token_xla`` and a mask."""
        import jax
        import jax.numpy as jnp

        s0 = state["ssm"]
        kernels = ssm.ssm_rule(*s0.shape[1:], s0.dtype)
        if kernels and live is None:
            live = jnp.ones(s0.shape[:1], bool)
        with jax.named_scope(SSM_CONV_SCOPE):
            if kernels:
                u, tail = ssm.ssm_conv_update(
                    state["conv"], rows["u"], lw["ssm_conv"],
                    lw["ssm_conv_b"], live, interpret=interpret)
            else:
                u, tail = ssm.ssm_conv_xla(
                    state["conv"], rows["u"], lw["ssm_conv"],
                    lw["ssm_conv_b"])
                if live is not None:
                    tail = jnp.where(live[:, None], tail, state["conv"])
        dt, b, c, a = self._ssm_vectors(lw, u)
        with jax.named_scope(SSM_STEP_SCOPE):
            if kernels:
                y, s = ssm.ssm_update(dt, u, b, c, a, s0, live,
                                      interpret=interpret)
            else:
                y, s = ssm.ssm_token_xla(dt, u, b, c, a, s0)
                if live is not None:
                    s = jnp.where(live[:, None, None], s, s0)
        return y + lw["ssm_d"] * u, {"ssm": s, "conv": tail}

    def _ssm_chunk(self, lw, rows, n_real, state, interpret=False):
        """A GROUP of whole ``SCAN_CHUNK``-token tiles, consecutive
        tokens of ONE request, through a recurrent layer in one call of
        the scan kernel (``ops/pallas_ssm.py`` ``ssm_scan``: the token
        rule itself, the state in fast memory from the call's first
        token to its last): ``rows`` their in-projection (``u [N,
        d_inner]``), of which the first ``n_real`` are the request's
        (the kernel steps the rest by 0 and skips tiles of nothing else:
        padding touches neither the state nor the tail), ``state`` the
        request's before them (leading dimension 1) -> (``y + D u [N,
        d_inner]``, the state after token ``n_real - 1``).  The
        convolution, the projections and the norms are the token form's,
        over all the call's rows at once."""
        import jax
        import jax.numpy as jnp

        k, d = self.d_conv, self.d_inner
        with jax.named_scope(SSM_CONV_SCOPE):
            n = rows["u"].shape[0]
            window = jnp.concatenate(
                [state["conv"].reshape(k - 1, d), rows["u"]])
            u = jax.nn.silu(lw["ssm_conv_b"] + sum(
                window[j:j + n] * lw["ssm_conv"][j] for j in range(k)))
            tail = jax.lax.dynamic_slice_in_dim(window, n_real, k - 1)
        dt, b, c, a = self._ssm_vectors(lw, u)
        with jax.named_scope(SSM_SCAN_SCOPE):
            y, s = ssm.ssm_scan(dt, u, b, c, a, state["ssm"], n_real,
                                interpret=interpret)
        return y + lw["ssm_d"] * u, {"ssm": s, "conv": tail.reshape(1, -1)}


class LatentMixer:
    """Latent attention of a model with ``num_heads`` heads of
    ``nope_dim + rope_dim`` query lanes and ``v_dim`` value lanes over a
    latent of ``kv_rank``, ``softmax_scale`` and ``rms_eps``: its
    weights, what the engine reads of it, and the two forms.  A model
    says how its queries are made (``_queries``, ``_query_weights``) and
    hands ``turn``, the rotary term's two factors, or None where its
    last ``rope_dim`` lanes carry no position (they are then lanes like
    the others: nothing turns them, not by the identity either).
    ``LatentMoELM``'s, and ``linear_latent_lm.py``'s."""

    def latent_declares(self):
        """What the engine reads: ONE cached row a position, all heads'."""
        self.num_kv_heads = 1
        self.head_dim = self.kv_rank + self.rope_dim
        self.v_head_dim = self.kv_rank
        self.values_in_keys = True
        self.prompt_heads = (self.num_heads, self.nope_dim + self.rope_dim,
                             self.v_dim)

    def latent_weights(self, dense, ones):
        """A latent layer's mixer weights, the queries' first."""
        h = self.num_heads
        up = 1.0 / math.sqrt(self.kv_rank)
        return {**self._query_weights(dense, ones),
                "kv_norm": ones(self.kv_rank),
                "wkv_a": dense((self.d_model, self.kv_rank + self.rope_dim)),
                # kv_b_proj, split once: head h's keys are c W_UK[h]^T,
                # its values c W_UV[h]
                "w_uk": dense((h, self.nope_dim, self.kv_rank), up),
                "w_uv": dense((h, self.kv_rank, self.v_dim), up),
                "wo": dense((h * self.v_dim, self.d_model))}

    def _attention(self, lw, l, h, turn, cache, attend):
        """One layer's context ``[..., H, v_dim]`` of rows ``h``, in the
        form the program asks for; ``turn``: the class docstring."""
        import jax
        import jax.numpy as jnp

        lead, nh = h.shape[:-1], self.num_heads
        with jax.named_scope(Q_PROJ_SCOPE):
            q = self._queries(lw, h).reshape(
                *lead, nh, self.nope_dim + self.rope_dim)
        with jax.named_scope(KV_PROJ_SCOPE):
            c, k_r = self._latent(lw, _mm(h, lw["wkv_a"]))
        if turn is None:
            q_rot, k_rot = q[..., self.nope_dim:], k_r[..., None, :]
        else:
            with jax.named_scope(ROPE_SCOPE):
                q_rot = self._rotate(q[..., self.nope_dim:], *turn)
                k_rot = self._rotate(k_r[..., None, :], *turn)
        q_nope = q[..., :self.nope_dim]
        row = jnp.concatenate([c[..., None, :], k_rot], axis=-1)
        dt = lw["w_uk"].dtype
        if attend.prompt:
            with jax.named_scope(EXPAND_SCOPE):
                cb = c.astype(dt)
                k_nope = jnp.einsum("...c,hdc->...hd", cb, lw["w_uk"],
                                    preferred_element_type=jnp.float32)
                v = jnp.einsum("...c,hcd->...hd", cb, lw["w_uv"],
                               preferred_element_type=jnp.float32)
                k = jnp.concatenate([k_nope, jnp.broadcast_to(
                    k_rot, (*lead, nh, self.rope_dim))], axis=-1)
            return attend(l, self._scaled(
                jnp.concatenate([q_nope, q_rot], axis=-1)), k, v, cache,
                keep=row)
        with jax.named_scope(ABSORB_Q_SCOPE):
            q_lat = jnp.einsum("...hd,hdc->...hc", q_nope.astype(dt),
                               lw["w_uk"],
                               preferred_element_type=jnp.float32)
        ctx_lat, cache = attend(l, self._scaled(
            jnp.concatenate([q_lat, q_rot], axis=-1)), row, None, cache)
        with jax.named_scope(ABSORB_V_SCOPE):
            return jnp.einsum("...hc,hcd->...hd", ctx_lat.astype(dt),
                              lw["w_uv"],
                              preferred_element_type=jnp.float32), cache

    def _latent(self, lw, kv):
        """(c, k_r) of ``kv = h W_kva``: the norm is the latent's alone,
        the shared key is not normed."""
        return rms_norm(kv[..., :self.kv_rank], lw["kv_norm"],
                        self.rms_eps), kv[..., self.kv_rank:]

    def _scaled(self, q):
        """The engine's attention divides scores by the square root of
        the query's width; what the softmax's scale holds beyond that
        (the heads' own width, YaRN's ``mscale^2``) rides the query."""
        return q * (self.softmax_scale * math.sqrt(q.shape[-1]))


class IndexCall:
    """What an indexed layer hands ``attend`` as ``index=``: the rows'
    index ``key`` (``[..., 1, index_dim]``: what the third pool keeps of
    them), and the indexer's two forms with the call's queries bound.
    ``step(keys, lengths, rows) -> (positions, ok, rows)`` over each
    slot's cached keys ``[S, N, lanes]`` as the pool lays them out,
    ``rows [S, N]`` whatever the caller wants back at the selected
    positions (where a position's K and V lie); ``prompt(q, k, v,
    keys, length) -> ctx`` over one prompt's own rows."""

    def __init__(self, key, step, prompt):
        self.key, self.step, self.prompt = key, step, prompt


class IndexedMixer:
    """Grouped-query attention (``num_heads`` on ``num_kv_heads`` of
    ``head_dim``, an RMSNorm on every q and k head, half-split rotary on
    all lanes at ``rope_theta``) whose softmax runs over the
    ``index_topk`` positions an indexer of ``index_heads`` heads of
    ``index_dim`` lanes on ONE key head selects, a layer its own.  A
    prompt's rows are taken ``index_block`` at a time.  ``IndexedMoELM``'s."""

    def indexed_weights(self, dense, ones, keys):
        """An indexed layer's mixer weights.  The index key's LayerNorm
        has a bias, seeded small and not zero so that leaving it out
        shows."""
        import jax
        import jax.numpy as jnp

        dm = self.d_model
        hq = self.num_heads * self.head_dim
        hkv = self.num_kv_heads * self.head_dim
        return dict(
            wq=dense((dm, hq)), wk=dense((dm, hkv)), wv=dense((dm, hkv)),
            wo=dense((hq, dm)), q_norm=ones(self.head_dim),
            k_norm=ones(self.head_dim),
            index_wq=dense((dm, self.index_heads * self.index_dim)),
            index_wk=dense((dm, self.index_dim)),
            index_ww=dense((dm, self.index_heads)),
            index_k_gain=ones(self.index_dim),
            index_k_bias=0.1 * jax.random.normal(
                next(keys), (self.index_dim,), jnp.float32))

    def _attention(self, lw, l, h, positions, cache, attend):
        """One layer's output ``[..., D]`` of rows ``h``: q and k normed
        a head and turned, the indexer's queries, key and head weights
        formed from the same rows, attended through the engine."""
        import jax
        import jax.numpy as jnp

        lead = h.shape[:-1]
        q = _mm(h, lw["wq"]).reshape(*lead, self.num_heads, self.head_dim)
        k = _mm(h, lw["wk"]).reshape(*lead, self.num_kv_heads,
                                     self.head_dim)
        v = _mm(h, lw["wv"]).reshape(*lead, self.num_kv_heads,
                                     self.head_dim)
        q, k = self._qk_norm(lw, q, k)
        with jax.named_scope(INDEX_PROJ_SCOPE):
            qi = _mm(h, lw["index_wq"]).reshape(
                *lead, self.index_heads, self.index_dim)
            ki = self._index_key(lw, _mm(h, lw["index_wk"]))[..., None, :]
            w = self._index_weights(lw, h)
        with jax.named_scope(ROPE_SCOPE):
            turn = self._rotary(positions, self.head_dim)
            q, k = self._rotate(q, *turn), self._rotate(k, *turn)
            qi, ki = self._index_rotary(qi, ki, positions)
        ctx, cache = attend(l, q, k, v, cache, index=IndexCall(
            ki, functools.partial(self._index_step, attend, qi, w),
            functools.partial(self._index_prompt, attend, qi, w)))
        return _mm(ctx.reshape(*lead, -1).astype(jnp.float32),
                   lw["wo"]), cache

    # -- the seams a departure is made at -----------------------------------
    def _qk_norm(self, lw, q, k):
        return rms_norm(q, lw["q_norm"], self.rms_eps), \
            rms_norm(k, lw["k_norm"], self.rms_eps)

    def _rotary(self, positions, width):
        return half_split_angles(positions, self.rope_theta, width)

    _rotate = staticmethod(half_split_rotate)

    def _index_rotary(self, qi, ki, positions):
        """The indexer's queries and key carry the same rotary term on
        their ``index_dim`` lanes."""
        turn = self._rotary(positions, self.index_dim)
        return self._rotate(qi, *turn), self._rotate(ki, *turn)

    def _index_key(self, lw, x):
        """LayerNorm (mean removed, a gain and a bias) of the one index
        key, float32."""
        import jax
        import jax.numpy as jnp

        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + self.rms_eps) \
            * lw["index_k_gain"] + lw["index_k_bias"]

    def _index_weights(self, lw, h):
        """A row's weight of each indexer head."""
        return _mm(h, lw["index_ww"])

    def _index_relu(self, s):
        import jax

        return jax.nn.relu(s)

    def _index_scores(self, qi, w, keys):
        """``I`` of queries ``qi [..., Hi, Di]`` weighted ``w`` over
        ``keys [..., N, lanes]`` (``lanes >= Di``: a pool's row is a key
        and zeros, which a zero-padded query meets)."""
        import jax.numpy as jnp

        short = keys.shape[-1] - qi.shape[-1]
        if short:
            qi = jnp.pad(qi, ((0, 0),) * (qi.ndim - 1) + ((0, short),))
        return ixa.index_scores(qi, w, keys, relu=self._index_relu)

    # -- the indexer's two forms --------------------------------------------
    def _index_step(self, attend, qi, w, keys, lengths, rows):
        """The step: slot s's query against its cached keys ``keys [S,
        N, lanes]`` of which the first ``lengths[s]`` are live ->
        (positions ``[S, topk]``, ok, ``rows [S, N]`` at them)."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope(INDEX_SCORE_SCOPE):
            scores = self._index_scores(qi, w, keys)
        with jax.named_scope(INDEX_SELECT_SCOPE):
            pos, ok, rows = ixa.select_top(
                scores, lengths, self.index_topk, carry=(rows,))
        attend.record(INDEX_RECORD, jnp.where(ok, pos, -1))
        return pos, ok, rows

    def _index_prompt(self, attend, qi, w, q, k, v, keys, length):
        """One prompt's rows ``q [T, H, D]`` over its own ``k`` / ``v
        [T, Hkv, D]`` and index ``keys [T, Di]`` (each as the pools keep
        them) -> ``ctx [T, H, D]``: causal attention in whatever form
        the engine runs a prompt's (``attend.causal``), under a
        selection a pair where some row has more than ``index_topk``
        positions to choose from.  Those rows go ``index_block`` at a
        time (the block's scores over the whole bucket, each row's mask);
        the whole blocks of rows under ``index_topk`` attend every
        earlier position."""
        import jax
        import jax.numpy as jnp

        t, blk = q.shape[0], min(self.index_block, q.shape[0])
        plain = min(self.index_topk // blk * blk, t)
        bits = jnp.zeros((plain, t // 8), jnp.uint8)
        if plain == t:
            attend.record(INDEX_RECORD, bits)
            return attend.causal(q, k, v, length)
        if (t - plain) % blk:
            raise ValueError(f"a prompt bucket of {t} rows is not whole "
                             f"blocks of {blk} past row {plain}")
        col = jnp.arange(t, dtype=jnp.int32)[None, :]

        def block(first):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                a, first, blk, axis=0)
            row = first + jnp.arange(blk, dtype=jnp.int32)[:, None]
            with jax.named_scope(INDEX_SCORE_PROMPT_SCOPE):
                scores = self._index_scores(sl(qi), sl(w), keys)
            with jax.named_scope(INDEX_SELECT_PROMPT_SCOPE):
                mask = ixa.select_mask(scores, col <= row, self.index_topk)
                return mask.astype(jnp.int8), ixa.pack_bits(mask)

        chosen, packed = jax.lax.map(block, jnp.arange(
            plain, t, blk, dtype=jnp.int32))
        attend.record(INDEX_RECORD, jnp.concatenate(
            [bits, packed.reshape(t - plain, t // 8)]))
        with jax.named_scope(SPARSE_ATTN_PROMPT_SCOPE):
            return attend.causal(q, k, v, length, select=jnp.concatenate(
                [jnp.ones((plain, t), jnp.int8),
                 chosen.reshape(t - plain, t)]))
