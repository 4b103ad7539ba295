"""Chip smoke: the program's main paths, once, on a real TPU.

    python chip_smoke.py            # one chip: BERT-base trainer, ResNet-50
                                    # trainer, GPT-2-width DecodeServer
    python chip_smoke.py --chips 4  # four chips: BERT-base fleet data
                                    # parallel against the one-chip run

One process; it refuses to start unless jax's first device is a TPU, and
the first phase that fails ends the run with a non-zero exit code.
Models run at published width with random weights from a fixed seed;
depth, batch and step counts are what a smoke needs, not a benchmark.
The server is checked twice: tokens against a plain full-recompute
reference at ``highest`` matmul precision, then the step it really
serves (default precision) against the engine's own reference step.
Every line printed is one JSON object; the LAST line is the verdict

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Nothing here is a performance claim: times are printed so that the next
reader can see compile seconds apart from step seconds, no more.
"""
import argparse
import glob
import json
import os
import shutil
import time
import urllib.request

import numpy as np

SEED = 0

# published widths (depth/batch may be cut by a test; widths may not)
BERT = dict(batch_size=256, seq_len=128, vocab_size=30522, hidden=768,
            n_layers=12, n_heads=12, ffn_size=3072, max_preds_per_seq=20)
RESNET = dict(batch_size=128, img_shape=(3, 224, 224), class_num=1000)
# a rate of 0.1 is the peak of a warmed-up schedule on real data; on one
# fixed random batch it overshoots for the first steps (seen at a small
# size on the CPU), and this smoke asserts a falling loss
RESNET_LR = 0.02
GPT2 = dict(vocab_size=50257, d_model=768, num_layers=12, num_heads=12,
            ffn_dim=3072, max_seq_len=1024)
TRAIN_CALLS, TRAIN_STEPS = 3, 4          # run_steps calls x steps each
PROMPT_LENS = (300, 40, 7, 130)          # mixed; all in flight together
NEW_TOKENS = 8
DP_BERT_BATCH = 64                       # four-chip phase: global batch
DP_STEPS = 4
# __graft_entry__'s f32 dry run holds 1e-4.  This program is bf16 AMP: one
# chip reduces B rows in one matmul, four chips reduce B/4 rows each and
# all-reduce, so bf16 products are summed in another order and the
# difference feeds back through every AdamW step (read on the chip:
# 5e-6 at step 1, 9.9e-5 at step 4).  One bf16 ulp is 3.9e-3; 3e-4 keeps
# the bar well under it and off the fourth step's own rounding.
DP_LOSS_RTOL = 3e-4
# served-precision round: kernel step against the engine's own reference
# step, worst |logit difference| over the largest |logit|.  On the chip
# f32 operands take one bf16 pass by default and the two steps round
# their attention matmuls apart; a wrong page or mask is O(1).
SERVED_LOGIT_RTOL = 5e-2
HLO_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out", "hlo_dp")


def emit(**kw):
    print(json.dumps(kw), flush=True)


def require_tpu(n_chips):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; jax found {devs[0].platform!r} "
            f"({len(devs)} device(s)) - nothing was run")
    if len(devs) != n_chips:
        raise SystemExit(
            f"chip_smoke: asked for {n_chips} chip(s), jax sees {len(devs)}")
    return devs


def device_line(devs):
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def cache_entries():
    import jax

    d = jax.config.jax_compilation_cache_dir
    return d, (len(os.listdir(d)) if d and os.path.isdir(d) else 0)


# ---------------------------------------------------------------------------
# trainers


def bert_program(batch_size, fleet_dp=False, **overrides):
    """BERT-base pretraining step as the benchmark's cell has it (bf16 AMP,
    AdamW); ``fleet_dp`` routes minimize through the fleet collective
    optimizer (c_allreduce_sum grads)."""
    from paddle_tpu.amp.static_amp import decorate
    from paddle_tpu.framework.program import program_guard
    from paddle_tpu.text import bert_base_pretrain_program

    cfg = dict(BERT, batch_size=batch_size, **overrides)
    main_p, startup, _, loss, opt = bert_base_pretrain_program(**cfg)
    main_p.random_seed = 1
    with program_guard(main_p, startup):
        opt = decorate(opt, use_bf16=True)
        if fleet_dp:
            from paddle_tpu.distributed import fleet

            fleet.init(is_collective=True)
            fleet.distributed_optimizer(opt)
            fleet.minimize(loss)
        else:
            opt.minimize(loss)
    return main_p, startup, loss


def bert_feed(batch_size, shards=1):
    """One fixed synthetic batch.  ``masked_flat_pos`` indexes the
    flattened [batch*seq] activations of the program that consumes it,
    so under data parallelism it is local to each shard's slice."""
    S, P, V = BERT["seq_len"], BERT["max_preds_per_seq"], BERT["vocab_size"]
    B = batch_size
    rng = np.random.RandomState(SEED)
    ids = rng.randint(0, V, (B, S)).astype("int64")
    pos = np.stack([rng.choice(S, P, replace=False) for _ in range(B)])
    labels = np.take_along_axis(ids, pos, axis=1).reshape(-1, 1)
    local_b = np.arange(B) % (B // shards)
    return {
        "input_ids": ids,
        "token_type_ids": np.zeros((B, S), "int64"),
        "pos_ids": np.tile(np.arange(S, dtype="int64"), (B, 1)),
        "input_mask": np.zeros((B, 1, 1, S), "float32"),
        "masked_flat_pos": (local_b[:, None] * S + pos).reshape(-1)
        .astype("int64"),
        "masked_labels": labels.astype("int64"),
        "masked_weights": np.ones((B * P, 1), "float32"),
        "nsp_labels": rng.randint(0, 2, (B, 1)).astype("int64"),
    }


def resnet_program():
    from paddle_tpu.amp.static_amp import decorate
    from paddle_tpu.framework.program import program_guard
    from paddle_tpu.vision.static_models import resnet50_train_program

    main_p, startup, _, loss, opt = resnet50_train_program(
        lr=RESNET_LR, momentum=0.9, img_shape=RESNET["img_shape"],
        class_num=RESNET["class_num"])
    main_p.random_seed = 1
    with program_guard(main_p, startup):
        decorate(opt, use_bf16=True).minimize(loss)
    return main_p, startup, loss


def resnet_feed():
    B = RESNET["batch_size"]
    rng = np.random.RandomState(SEED)
    return {
        "image": rng.randn(B, *RESNET["img_shape"]).astype("float32"),
        "label": rng.randint(0, RESNET["class_num"], (B, 1)).astype("int32"),
    }


def train_phase(name, program, feed, batch):
    """Startup program, then TRAIN_CALLS run_steps calls of TRAIN_STEPS
    steps on one fixed batch through Executor(TPUPlace(0)).  Loss must be
    finite at every step and lower at the end than at the start."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.observe import xla_stats

    main_p, startup, loss = program
    dev = jax.devices()[0]
    exe = pt.Executor(pt.TPUPlace(0))
    scope = pt.framework.Scope()
    t0 = time.perf_counter()
    exe.run(startup, scope=scope)
    exe.drain()
    startup_s = time.perf_counter() - t0
    feed = {k: jax.device_put(v, dev) for k, v in feed.items()}
    losses, call_s = [], []
    for _ in range(TRAIN_CALLS):
        t0 = time.perf_counter()
        out = exe.run_steps(main_p, feed=feed, fetch_list=[loss],
                            scope=scope, steps=TRAIN_STEPS)
        vals = np.asarray(jax.block_until_ready(out[0]), "float64")
        call_s.append(time.perf_counter() - t0)
        losses.extend(float(v) for v in vals.reshape(-1))
    exe.close()
    assert np.isfinite(losses).all(), (name, losses)
    assert losses[-1] < losses[0], (name, "loss did not fall", losses)
    # the first call compiled (the executor's own AOT record says how
    # long); the later ones reuse that executable
    rec = xla_stats.last_compile() or {}
    emit(phase=name, batch=batch, steps=len(losses),
         startup_s=round(startup_s, 3),
         compile_s=rec.get("compile_seconds"),
         hbm_required_bytes=(rec.get("memory") or {}).get("total_bytes"),
         first_call_s=round(call_s[0], 3),
         step_s=round(float(np.median(call_s[1:])) / TRAIN_STEPS, 5),
         loss_first=losses[0], loss_last=losses[-1],
         losses=[round(v, 5) for v in losses], peak_bytes=peak_bytes(dev))
    return losses


# ---------------------------------------------------------------------------
# server


def reference_forward(model, weights, tokens, n_live):
    """The model's own forward over the WHOLE sequence with a plain
    jax.numpy ``attend``: no cache, no pages, no kernel - causal
    softmax attention written out.  ``tokens`` is padded to a fixed
    length; returns position ``n_live-1``'s logits."""
    import jax
    import jax.numpy as jnp

    t = tokens.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def attend(_layer, q, k, v, cache):
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(model.head_dim)
        p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v), cache

    logits, _ = model.forward(weights, tokens, jnp.arange(t), None, attend)
    return logits[n_live - 1]


def reference_greedy(model, weights, prompts, n_new):
    import jax
    import jax.numpy as jnp

    pad = 1 << (max(len(p) for p in prompts) + n_new - 1).bit_length()
    fwd = jax.jit(lambda w, t, n: reference_forward(model, w, t, n))
    outs = []
    for prompt in prompts:
        seq = list(prompt)
        for _ in range(n_new):
            buf = np.zeros((pad,), np.int32)
            buf[:len(seq)] = seq
            logits = fwd(weights, jnp.asarray(buf), np.int32(len(seq)))
            seq.append(int(np.argmax(np.asarray(logits))))
        outs.append(seq[len(prompt):])
    return outs


def assert_kernel_in_step(engine, label):
    """'auto' must have put the Pallas kernel into the decode step: a
    reference that quietly stood in is a failure."""
    text = engine.lower_step().as_text()
    assert "tpu_custom_call" in text, (
        label, "the decode step holds no Pallas kernel")


def serve(label, model, weights, config, prompts, want=None, kernel=True):
    """One DecodeServer over ``config``: all prompts in flight together
    through submit()/result(), one GET /stats over real HTTP, and the
    Pallas kernel present in the lowered decode step (``kernel=False``:
    absent - the engine's reference step as a witness).  With ``want``
    the greedy tokens must equal it; without, every step's logits are
    recorded and returned with the warm round's tokens."""
    import jax

    from paddle_tpu.serving import DecodeServer

    srv = DecodeServer(model, weights, config, http_port=0)
    if kernel:
        assert_kernel_in_step(srv.replicas[0], label)
    else:
        assert "tpu_custom_call" not in \
            srv.replicas[0].lower_step().as_text(), label
    round_s = []
    with srv:
        # round 1 compiles; round 2 is warm and finds its prompts in the
        # prefix cache (suffix prefill through the multi-row kernel)
        for _ in range(2):
            t0 = time.perf_counter()
            reqs = [srv.submit(p, max_new_tokens=NEW_TOKENS,
                               record_logits=want is None)
                    for p in prompts]
            got = [r.result(timeout=900) for r in reqs]
            round_s.append(time.perf_counter() - t0)
            assert want is None or got == want, (
                label, "tokens differ from the reference", got, want)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_port}/stats", timeout=30) as r:
            stats = json.loads(r.read())
    wall, warm = round_s
    n_tok = len(prompts) * NEW_TOKENS
    assert stats["tokens_total"] == 2 * n_tok, stats
    emit(phase=label, requests=2 * len(prompts),
         prompt_lens=[len(p) for p in prompts], new_tokens=NEW_TOKENS,
         matmul_precision=jax.config.jax_default_matmul_precision
         or "default",
         tokens_equal_reference=want is not None,
         pallas_in_decode_step=kernel,
         first_round_s=round(wall, 3), warm_round_s=round(warm, 3),
         warm_s_per_token=round(warm / n_tok, 5),
         prefill_chunks=stats["prefill_chunks"],
         cache_hit_rate=stats["cache_hit_rate"],
         tokens_total=stats["tokens_total"],
         peak_bytes=peak_bytes(jax.devices()[0]))
    return got, [np.asarray(r.logits_trace) for r in reqs]


def served_precision_round(model, weights, prompts):
    """The step ``DecodeConfig``'s defaults really serve: jax's default
    matmul precision.  There a full recompute rounds differently from a
    cached step and is no oracle for an argmax, so the witness is the
    engine's own ``use_pallas="never"`` step at the same precision: on
    the same history the two steps' logits must be finite and agree to
    ``SERVED_LOGIT_RTOL``.  A token may then differ only where the
    witness's own top two are closer than that disagreement; past such a
    tie the histories differ and the request is compared no further."""
    from paddle_tpu.serving import DecodeConfig

    seq = GPT2["max_seq_len"]
    toks_k, logits_k = serve(
        "server_served_precision", model, weights,
        DecodeConfig(max_seq_len=seq), prompts)
    toks_w, logits_w = serve(
        "server_served_precision_witness", model, weights,
        DecodeConfig(max_seq_len=seq, use_pallas="never"), prompts,
        kernel=False)
    worst, compared, agree = 0.0, 0, 0
    for tk, lk, tw, lw in zip(toks_k, logits_k, toks_w, logits_w):
        assert lk.shape == lw.shape == (NEW_TOKENS, GPT2["vocab_size"])
        assert np.isfinite(lk).all() and np.isfinite(lw).all()
        for j in range(NEW_TOKENS):
            err = float(np.abs(lk[j] - lw[j]).max() / np.abs(lw[j]).max())
            worst, compared = max(worst, err), compared + 1
            assert err <= SERVED_LOGIT_RTOL, (
                "kernel and witness logits differ", j, err, tk, tw)
            if tk[j] != tw[j]:
                break
            agree += 1
    emit(phase="server_served_precision_agreement", steps_compared=compared,
         tokens_agree=agree, worst_logit_rel_err=worst,
         rtol=SERVED_LOGIT_RTOL, tokens_kernel=toks_k, tokens_witness=toks_w)


def server_phase():
    import jax

    from paddle_tpu.serving import DecodeConfig
    from paddle_tpu.serving.decode import TransformerLM

    model = TransformerLM(**GPT2)
    weights = model.init_weights(jax.random.PRNGKey(SEED))
    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(0, GPT2["vocab_size"], n).tolist()
               for n in PROMPT_LENS]
    # f32 weights under the TPU's default matmul precision (one bf16
    # pass) make cached decode and a full recompute round differently;
    # with 50k random-weight logits an argmax tie would flip. Token
    # equality is a statement about the program, so hold both to f32.
    served = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        t0 = time.perf_counter()
        want = reference_greedy(model, weights, prompts, NEW_TOKENS)
        emit(phase="server_reference",
             seconds=round(time.perf_counter() - t0, 3), tokens=want)
        seq = GPT2["max_seq_len"]
        serve("server_decode_kernel", model, weights,
              DecodeConfig(max_seq_len=seq), prompts, want)
        serve("server_chunk_kernel", model, weights,
              DecodeConfig(max_seq_len=seq, prefill_chunk_pages=1), prompts,
              want)
    finally:
        jax.config.update("jax_default_matmul_precision", served)
    served_precision_round(model, weights, prompts)


# ---------------------------------------------------------------------------
# four chips: fleet data parallel against the one-chip run


def dp_phase(devs):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu.distributed.parallel_env import (init_parallel_env,
                                                     reset_mesh)

    n = len(devs)
    B = DP_BERT_BATCH
    # dropout draws differ between one program over B rows and n programs
    # over B/n rows, so parity is checked with it off: the data-parallel
    # machinery (sharded feed, replicated state, allreduced grads) is
    # what this phase is about
    no_dropout = {"dropout_prob": 0.0}
    hlo_dir = HLO_DIR

    def run(program, feed, mesh):
        main_p, startup, loss = program
        exe = pt.Executor(pt.TPUPlace(0), mesh=mesh)
        scope = pt.framework.Scope()
        exe.run(startup, scope=scope)
        losses = []
        for _ in range(DP_STEPS):
            out = exe.run(main_p, feed=feed, fetch_list=[loss], scope=scope)
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        exe.close()
        return losses, scope

    base, _ = run(bert_program(B, **no_dropout), bert_feed(B), None)

    mesh = init_parallel_env()
    assert mesh.axis_names == ("dp",) and mesh.devices.size == n, mesh
    # only THIS run's programs may answer for the all-reduce below
    shutil.rmtree(hlo_dir, ignore_errors=True)
    pt.set_flags({"FLAGS_hlo_dump_dir": hlo_dir})
    try:
        program = bert_program(B // n, fleet_dp=True, **no_dropout)
        ops = [op.type for op in program[0].global_block.ops]
        assert "c_allreduce_sum" in ops, "fleet DP transpile missing"
        # the feed arrives the way a data-parallel input pipeline hands
        # it over: batch dim split across the mesh
        feed = {k: jax.device_put(v, NamedSharding(mesh, P("dp")))
                for k, v in bert_feed(B, shards=n).items()}
        dist, scope = run(program, feed, mesh)
    finally:
        pt.set_flags({"FLAGS_hlo_dump_dir": ""})
        reset_mesh()
    # state and feed really live on n distinct chips
    state = [v for v in map(scope.get_var, scope.local_var_names())
             if isinstance(v, jax.Array)]
    w = max(state, key=lambda v: v.size)    # the word-embedding table
    x = feed["input_ids"]
    for arr in state + list(feed.values()):
        assert len({s.device for s in arr.addressable_shards}) == n, (
            arr.shape, arr.sharding)
    assert x.addressable_shards[0].data.shape[0] == B // n
    hlo = "".join(open(f).read()
                  for f in glob.glob(os.path.join(hlo_dir, "*.txt")))
    assert "all-reduce" in hlo, "no all-reduce in the compiled program"
    assert np.isfinite(dist).all() and np.isfinite(base).all(), (dist, base)
    np.testing.assert_allclose(dist, base, rtol=DP_LOSS_RTOL, atol=1e-6)
    emit(phase="bert_fleet_dp", chips=n, global_batch=B, steps=DP_STEPS,
         losses_one_chip=base, losses_dp=dist, rtol=DP_LOSS_RTOL,
         state_arrays=len(state), param_devices=len(w.sharding.device_set),
         feed_devices=len(x.sharding.device_set),
         all_reduce_ops_in_hlo=hlo.count(" all-reduce("),
         peak_bytes=[peak_bytes(d) for d in devs])


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    import jax

    from paddle_tpu import native

    t_start = time.perf_counter()
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def on_event(event, **_):
        key = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)
    emit(phase="start", device=device_line(devs), jax=jax.__version__,
         data_feed="native" if native.has_native() else "python",
         cache_dir_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if args.chips == 4:
        dp_phase(devs)
    else:
        train_phase("bert_base_train", bert_program(BERT["batch_size"]),
                    bert_feed(BERT["batch_size"]), BERT["batch_size"])
        train_phase("resnet50_train", resnet_program(), resnet_feed(),
                    RESNET["batch_size"])
        server_phase()
    cache_dir, n_entries = cache_entries()
    emit(phase="done", seconds=round(time.perf_counter() - t_start, 1),
         compile_cache_dir=cache_dir, compile_cache_entries=n_entries,
         compile_cache_hits=cache_events["cache_hits"],
         compile_cache_misses=cache_events["cache_misses"])
    print(json.dumps({"ok": True, "device": device_line(devs)}), flush=True)


if __name__ == "__main__":
    main()
