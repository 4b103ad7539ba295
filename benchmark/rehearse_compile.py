"""Compile each cell's step programs at REAL size for a described,
unattached ``v5e:2x2`` - by hand, here, before chip time is spent.

    python benchmark/rehearse_compile.py [--workload <cell> ...]
        [--overlay benchmark/tests/data/overlay_dp4] [--batch-per-chip N]

Nothing runs and nothing is timed: what the chip's compiler refuses (a
kernel it cannot tile, a program that does not fit 16 GB) shows here at
no chip time, and ``memory_analysis()`` gives each program's bytes.  A
compile that passes is not a chip run and is never reported as one.

Every line printed is one JSON object.  For a training cell: the
``run_steps`` program on one described chip, or over the described
chips as a 'dp' mesh, with its collectives counted.  For a serving
cell: the joint decode step (is ``tpu_custom_call`` in it?) and one
prefill program per bucket the traffic hits.

How: the program runs on the CPU up to the point where the executor (or
the engine) would compile; a hook takes the jitted function and its
arguments there, swaps the arguments for shapes placed on the described
devices, and compiles that.  Code that asks ``jax.default_backend()``
still sees the CPU, so the serving cells are steered to the Pallas
kernel (``use_pallas="always"``), as 'auto' picks it on the chip.
``--overlay`` adds a cell that is not in ``BENCHMARK.json`` yet (a
directory of files and manifest entries, see ``tests/overlay.py``).
"""
import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_MEMORY = ("argument_size_in_bytes", "output_size_in_bytes",
           "temp_size_in_bytes", "alias_size_in_bytes",
           "generated_code_size_in_bytes")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def emit(**kw):
    print(json.dumps(kw), flush=True)


def report(label, compile_fn, **extra):
    """Compile and print what the compiler says; a refusal is printed,
    not raised, so that the other programs are still tried."""
    t0 = time.perf_counter()
    try:
        compiled = compile_fn()
    except Exception as e:  # noqa: BLE001 - the refusal IS the result
        emit(program=label, compiled=False, error=str(e)[:2000], **extra)
        return None
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    mem = {k: getattr(ma, k, None) for k in _MEMORY}
    mem["total_bytes"] = sum(
        mem[k] or 0 for k in ("argument_size_in_bytes",
                              "output_size_in_bytes", "temp_size_in_bytes")
    ) - (mem["alias_size_in_bytes"] or 0)
    emit(program=label, compiled=True,
         compile_s=round(time.perf_counter() - t0, 1), memory=mem,
         tpu_custom_calls=text.count("tpu_custom_call"),
         collectives={c: text.count(f" {c}(") + text.count(f" {c}-start(")
                      for c in _COLLECTIVES}, **extra)
    return compiled


class _Captured(Exception):
    pass


def compile_train(cell, topo):
    """The cell's ``run_steps`` program for the described chip(s)."""
    import jax
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    import paddle_tpu as pt
    from paddle_tpu.framework.executor import Executor

    spec, config, chips = cell["spec"], cell["config"], cell["chips"]
    model, fleet_dp = cell["model"], bool(spec["train"].get("fleet_dp"))
    batch, feed = cell["traffic"].generate(
        spec["traffic"], model, config, 0, chips)
    mesh_cpu = mesh_tpu = None
    if fleet_dp:
        mesh_cpu = Mesh(np.array(jax.devices()[:chips]), ("dp",))
        mesh_tpu = Mesh(np.array(topo.devices[:chips]), ("dp",))
    main_p, startup, loss = model.build(config, batch // chips, 0,
                                        fleet_dp=fleet_dp)
    scope = pt.framework.Scope()
    pt.Executor(pt.TPUPlace(0), mesh=mesh_cpu).run(startup, scope=scope)

    taken = {}

    def hook(self, entry, program, mesh, args, *rest):
        taken.update(entry=entry, args=args)
        raise _Captured

    real = Executor._introspect_first_compile
    Executor._introspect_first_compile = hook
    try:
        pt.Executor(pt.TPUPlace(0), mesh=mesh_tpu).run_steps(
            main_p, feed=feed, fetch_list=[loss], scope=scope,
            steps=int(spec["train"]["steps_per_call"]))
    except _Captured:
        pass
    finally:
        Executor._introspect_first_compile = real
    feed_vals, mut_vals, const_vals, rng = taken["args"]

    if mesh_tpu is None:
        one = SingleDeviceSharding(topo.devices[0])
        place_feed = place_state = one
    else:
        place_feed = NamedSharding(mesh_tpu, P("dp"))
        place_state = NamedSharding(mesh_tpu, P())

    def shapes(tree, sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=sharding), tree)

    args = (shapes(feed_vals, place_feed), shapes(mut_vals, place_state),
            shapes(const_vals, place_state), shapes(rng, place_state))
    return report(f"{cell['name']}:run_steps",
                  lambda: taken["entry"].jit_fn.lower(*args).compile(),
                  chips=chips, batch_per_chip=batch // chips)


def compile_serve(cell, topo):
    """The cell's decode step and its prefill programs for one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.serving import DecodeEngine
    from paddle_tpu.serving.buckets import prefill_bucket_grid

    spec, config, model_mod = cell["spec"], cell["config"], cell["model"]
    one = SingleDeviceSharding(topo.devices[0])
    model = model_mod.make_model(config)
    weights = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init_weights, jax.random.PRNGKey(0)))
    dcfg = model_mod.decode_config(config, use_pallas="always")
    eng = DecodeEngine(model, weights, dcfg)
    report(f"{cell['name']}:decode_step",
           lambda: eng.lower_step(sharding=one).compile(), slots=dcfg.slots)

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                           sharding=one), tree)

    grid = prefill_bucket_grid(dcfg.max_seq_len, dcfg.page_size)
    lens = {p for p, _ in cell["traffic"].size_pool(spec["traffic"])}
    for t_pad in sorted({next(b for b in grid if b >= n) for n in lens}):
        state = tuple(eng._scope.get_var(n) for n in eng._state_vars)
        args = shapes((state, eng.weights, np.zeros((t_pad,), np.int32),
                       np.int32(1), np.asarray(eng._cache.page_table[0]),
                       jax.random.PRNGKey(0), np.float32(0), np.int32(0),
                       np.float32(1)))
        fn = eng._prefill_fn(t_pad)
        report(f"{cell['name']}:prefill_{t_pad}",
               lambda: fn.lower(*args).compile())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="a cell's name (default: every cell)")
    ap.add_argument("--overlay", help="a directory that adds a cell")
    ap.add_argument("--batch-per-chip", type=int,
                    help="try a training cell at another batch a chip")
    args = ap.parse_args(argv)

    from jax.experimental import topologies

    from benchmark import run as bench_run

    root = ROOT
    with tempfile.TemporaryDirectory() as tmp:
        if args.overlay:
            from benchmark.tests.overlay import apply_overlay

            root = apply_overlay(ROOT, args.overlay, tmp)
        manifest = bench_run.load_json(os.path.join(root, "BENCHMARK.json"))
        names = args.workload or [w["name"] for w in manifest["workloads"]]
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        emit(topology="v5e:2x2", devices=len(topo.devices),
             kind=topo.devices[0].device_kind, cells=names,
             note="a compile, not a chip run")
        for name in names:
            cell = bench_run.resolve_cell(root, name)
            kind = cell["spec"]["kind"]
            if args.batch_per_chip and kind == "train":
                cell["spec"]["traffic"]["batch_per_chip"] = \
                    args.batch_per_chip
            {"train": compile_train, "serve": compile_serve}[kind](cell, topo)


if __name__ == "__main__":
    main()
