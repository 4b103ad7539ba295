"""A training cell: the model's step through
``Executor(TPUPlace(0)).run_steps`` - the program's normal path - on one
chip, or on ``chips`` chips as fleet data parallel over a 'dp' mesh
(``chip_smoke.py``'s ``dp_phase`` is where the multi-chip set-up was
copied from).

Workload file: ``traffic`` (see the generator), ``train``:
``steps_per_call`` (a fixed number, so that one program serves every
run), ``fleet_dp``; ``check``: ``loss_rtol`` and ``probe_rtol`` with
their reasons.

Set-up: programs built, startup program run (weights from the seed),
the batch put on the device(s), the correctness check, one warm-up call.
Window: ``run_steps`` calls back to back, each ending in
``block_until_ready``, until ``--seconds`` are used up; the rate is all
samples of the window over all its time.
"""
import time

import numpy as np


def _param_values(program, scope):
    return {p.name: scope.get_var(p.name)
            for p in program.all_parameters()}


def check_forward(bench, exe, scope, main_p, loss, feed, ref_feed):
    """``main_p.clone(for_test=True)`` (forward only, dropout off, the
    same AMP casts), run once through the executor before training:
    its loss and the model's probe variables against the plain float32
    reference on the same weights and batch.  The loss is held to
    ``loss_rtol``; a probe to ``probe_rtol`` as the root-mean-square
    difference over the reference's root mean square."""
    import jax

    probes = bench.model.probes(bench.config, main_p)
    test_p = main_p.clone(for_test=True)
    out = exe.run(test_p, feed=feed, fetch_list=[loss, *probes.values()],
                  scope=scope, use_prune=True)
    out = [np.asarray(jax.block_until_ready(v), "float32") for v in out]
    weights = _param_values(main_p, scope)
    if bench.cell["chips"] > 1:
        # replicated parameters: the reference reads one chip's copy
        weights = {k: v.addressable_shards[0].data
                   for k, v in weights.items()}
    dev = bench.devices[0]
    want = bench.model.reference(
        bench.config, weights,
        {k: jax.device_put(v, dev) for k, v in ref_feed.items()})
    chk = bench.spec["check"]
    got_loss, want_loss = float(out[0].reshape(-1)[0]), float(want["loss"])
    loss_err = abs(got_loss - want_loss) / abs(want_loss)
    checks = {"loss_program": got_loss, "loss_reference": want_loss,
              "loss_rel_err": loss_err, "loss_rtol": chk["loss_rtol"],
              "probe_rtol": chk["probe_rtol"]}
    ok = bool(np.isfinite(got_loss) and loss_err <= chk["loss_rtol"])
    for name, got in zip(probes, out[1:]):
        ref = np.asarray(want[name], "float32").reshape(got.shape)
        err = float(np.sqrt(np.mean(np.square(got - ref))
                            / np.mean(np.square(ref))))
        checks[name + "_rel_err"] = err
        ok = ok and bool(np.isfinite(got).all()) and err <= chk["probe_rtol"]
    return ok, checks


def run(bench):
    import jax

    import paddle_tpu as pt

    spec, config, chips = bench.spec, bench.config, bench.cell["chips"]
    tr = spec["train"]
    steps = int(tr["steps_per_call"])
    fleet_dp = bool(tr.get("fleet_dp", False))
    seed_weights, seed_data = bench.seeds(2)

    mesh = None
    if fleet_dp:
        from paddle_tpu.distributed.parallel_env import init_parallel_env

        mesh = init_parallel_env()
        if mesh.axis_names != ("dp",) or mesh.devices.size != chips:
            raise RuntimeError(f"want a 'dp' mesh of {chips}, got {mesh}")
    elif chips != 1:
        raise RuntimeError("more than one chip needs train.fleet_dp")

    batch, feed_np = bench.traffic.generate(
        spec["traffic"], bench.model, config, seed_data, chips)
    main_p, startup, loss = bench.model.build(
        config, batch // chips, seed_weights, fleet_dp=fleet_dp)
    exe = pt.Executor(pt.TPUPlace(0), mesh=mesh)
    scope = pt.framework.Scope()
    t0 = time.perf_counter()
    exe.run(startup, scope=scope)
    exe.drain()
    startup_s = time.perf_counter() - t0

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        place = NamedSharding(mesh, P("dp"))
        # the reference indexes the whole batch, not one shard's slice
        ref_feed = bench.model.feed(config, batch, seed_data, shards=1)
    else:
        place, ref_feed = bench.devices[0], feed_np
    feed = {k: jax.device_put(v, place) for k, v in feed_np.items()}

    t0 = time.perf_counter()
    loss_ok, checks = check_forward(bench, exe, scope, main_p, loss,
                                    feed, ref_feed)
    check_s = time.perf_counter() - t0

    def call():
        t_call = time.perf_counter()
        out = exe.run_steps(main_p, feed=feed, fetch_list=[loss],
                            scope=scope, steps=steps)
        t_back = time.perf_counter()
        vals = np.asarray(jax.block_until_ready(out[0]), "float64")
        return t_back - t_call, time.perf_counter() - t_call, \
            vals.reshape(steps, -1).mean(axis=1)

    t0 = time.perf_counter()
    _, _, warm_losses = call()                  # compiles, or hits the cache
    warm_s = time.perf_counter() - t0
    bench.emit(phase="setup", startup_s=startup_s, check_s=check_s,
               first_call_s=warm_s, batch=batch, steps_per_call=steps,
               checks=checks)

    enqueue_s, call_s, losses = [], [], []
    seconds = bench.window_seconds()
    with bench.window():
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < seconds:
            with bench.span("run_steps_call", call=len(call_s)):
                e, c, vals = call()
            enqueue_s.append(e)
            call_s.append(c)
            losses.append(vals)
        elapsed = time.perf_counter() - t_open
    exe.close()
    if mesh is not None:
        from paddle_tpu.distributed.parallel_env import reset_mesh

        reset_mesh()

    losses = np.concatenate(losses)
    n_steps = len(call_s) * steps
    finite = np.isfinite(losses)
    fell = bool(losses[-steps:].mean() < warm_losses.mean())
    samples_s_chip = n_steps * batch / elapsed / chips
    m = config["model"]
    bench.emit(phase="window", calls=len(call_s), steps=n_steps,
               elapsed_s=elapsed, call_s=call_s,
               tokens_s_chip=samples_s_chip * m.get("seq_len", 0),
               loss_first_call=float(warm_losses.mean()),
               loss_last_call=float(losses[-steps:].mean()),
               loss_trajectory=[float(v) for v in losses[::steps]])
    checks.update(loss_fell=fell, losses_finite=int(finite.sum()))
    return {
        "correct": loss_ok and fell and bool(finite.all()),
        "attempted": n_steps,
        "failed": int((~finite).sum()),
        "end_to_end": {"train_samples_s_chip": samples_s_chip},
        "checks": checks,
        "info": {"startup_s": startup_s, "check_s": check_s,
                 "first_call_s": warm_s,
                 "feed_devices": len(
                     next(iter(feed.values())).sharding.device_set)},
        "sources": {
            "train": {
                "steps": n_steps, "calls": len(call_s),
                "elapsed_s": elapsed, "enqueue_s": enqueue_s,
                "call_s": call_s, "chips": chips,
                "flops_per_step_per_chip":
                    bench.model.flops_per_sample(config) * batch / chips,
            },
        },
    }
