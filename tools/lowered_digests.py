"""sha256 of the programs the cells' engines lower, for the described
chip, with no chip: what says whether a change moved an accepted cell's
programs.

    JAX_PLATFORMS=cpu python -m tools.lowered_digests [--root DIR]

Builds ``tests/test_tpu_compile.py``'s engines (the cells' widths, depth
cut, weights zero) from the checkout at ``--root`` (this one by
default; a ``git archive`` of the parent for the other side) and prints
one JSON line a program: its name and the sha256 of
``lower_step(sharding=one_chip)`` / ``lower_prefill(bucket,
sharding=one_chip)``'s text.  The same list in the same order on both
sides (programs lowered later in a process differ from the same program
lowered first: helper names count up).  A Pallas kernel's serialized
body is MLIR bytecode with source paths and lines in it, so two
checkouts never agree on it: each is parsed and replaced by the sha256
of its text without debug information.
"""
import argparse
import base64
import hashlib
import json
import os
import re
import sys

# (engine builder in tests/test_tpu_compile.py, its arguments, buckets)
PROGRAMS = (
    ("_gpt2_width_engine", (12, False), (128,)),
    ("_hybrid_engine", (), (128,)),
    ("_parallel_engine", (), (4096,)),
    ("_gated_delta_engine", (), (4096,)),
    ("_window_engine", (), (2048,)),
    ("_latent_engine", (), (8192,)),
    ("_looped_engine", (), (128,)),
    ("_conv_moe_engine", (), (2048,)),
    ("_linear_latent_engine", (), (4096,)),
    ("_mamba_engine", (), (512,)),
)
_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _kernel_free(text):
    """``text`` with every Pallas kernel's body replaced by the digest of
    its MLIR without locations."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def digest(m):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            tpu.register_dialect(ctx)
            mod = ir.Module.parse(base64.b64decode(m.group(2)))
            asm = mod.operation.get_asm(enable_debug_info=False)
        return m.group(1) + hashlib.sha256(asm.encode()).hexdigest() \
            + m.group(3)

    return _BODY.sub(digest, text)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [root, os.path.join(root, "tests")]
    for name in [n for n in sys.modules
                 if n.split(".")[0] in ("paddle_tpu", "benchmark")]:
        del sys.modules[name]

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import test_tpu_compile as cases

    assert os.path.abspath(cases.__file__).startswith(root), cases.__file__
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for builder, build_args, buckets in PROGRAMS:
        eng = getattr(cases, builder)(*build_args)
        lowered = [("step", eng.lower_step(sharding=one_chip))] + [
            (f"prefill_{b}", eng.lower_prefill(b, sharding=one_chip))
            for b in buckets]
        for program, low in lowered:
            text = _kernel_free(low.as_text())
            print(json.dumps({
                "program": f"{builder}.{program}", "lines": text.count("\n"),
                "sha256": hashlib.sha256(text.encode()).hexdigest()}),
                flush=True)
        del eng


if __name__ == "__main__":
    main()
