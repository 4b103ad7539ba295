"""L11 tooling gates: API signature spec + op-registry compat check.

Reference parity: tools/print_signatures.py + check_api_approvals.sh
(signature diffs need deliberate approval) and tools/check_op_desc.py /
op_version_registry (removing an op breaks saved programs).
"""
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", script), *args],
        capture_output=True, text=True, env=env)


def test_api_spec_is_current():
    """Any public-signature change must ship an updated API.spec in the
    same commit (run tools/print_signatures.py --update)."""
    p = _run("print_signatures.py", "--check")
    assert p.returncode == 0, p.stderr


def test_op_registry_never_shrinks():
    """Ops may be added freely; removing one breaks saved programs and
    must fail the gate."""
    p = _run("check_op_desc.py", "--check")
    assert p.returncode == 0, p.stderr


def test_op_spec_counts_grads():
    spec = open(os.path.join(ROOT, "OPS.spec")).read().splitlines()
    assert len(spec) >= 350
    kinds = {ln.split()[1] for ln in spec}
    assert kinds <= {"explicit_grad", "grad_maker", "generic_vjp"}


# ---------------------------------------------------------------------------
# the documents name files that exist
# ---------------------------------------------------------------------------

_PATH_TOKEN = re.compile(r"^([\w./\-]+\.(?:py|json|md|sh))(?:[:,.;)].*)?$")


@pytest.mark.parametrize("doc", [
    "README.md", "COVERAGE.md", "PERF.md", "METRICS.md", "BASELINE.md",
    ".claude/skills/verify/SKILL.md"])
def test_every_path_a_document_names_exists(doc):
    """A backticked word that looks like a file of this tree (`*.py`,
    `*.json`, `*.md`, `*.sh`) resolves under the root, `paddle_tpu/`,
    `benchmark/` or `tests/`, so a deleted file cannot live on in the
    manual.  Not judged: an absolute path, and a word with a placeholder
    in it (`<bundle>/requests.json`, `<train.py>`).  A path of another
    tree (the reference's, a model hub's) goes without backticks.
    ROADMAP.md and CHANGES.md are history and are not read."""
    with open(os.path.join(ROOT, doc)) as f:
        fences = re.split(r"```", f.read())   # odd pieces lie inside a fence
    spans = fences[1::2] + re.findall(r"`([^`]+)`", " ".join(fences[0::2]))
    words = (w.strip("()[],;\"'") for span in spans for w in span.split())
    named = {m.group(1) for m in map(_PATH_TOKEN.match, words)
             if m and not m.group(1).startswith("/")}
    missing = sorted(
        p for p in named if not any(
            os.path.exists(os.path.join(ROOT, base, p))
            for base in ("", "paddle_tpu", "benchmark", "tests")))
    assert not missing, f"{doc} names files that do not exist: {missing}"


# ---------------------------------------------------------------------------
# one process for each chip; a compile cache that is placed from outside
# ---------------------------------------------------------------------------


def _tiny_lm():
    import jax

    from paddle_tpu.serving.decode import TransformerLM

    model = TransformerLM(vocab_size=50, d_model=16, num_layers=1,
                          num_heads=2, max_seq_len=64)
    return model, model.init_weights(jax.random.PRNGKey(0))


def test_decode_server_replicas_land_on_distinct_devices():
    """DecodeServer(replicas=4) on (virtual) devices uses four of them:
    each replica's weights AND page pools are committed to its own
    device, and a request served there computes there."""
    import jax

    from paddle_tpu.serving import DecodeConfig, DecodeServer
    from paddle_tpu.serving.kv_cache import K_PAGES_VAR

    model, weights = _tiny_lm()
    srv = DecodeServer(model, weights,
                       DecodeConfig(max_seq_len=64, slots=2), replicas=4)
    want = jax.devices()[:4]
    assert [e.device for e in srv.replicas] == want
    for eng, dev in zip(srv.replicas, want):
        assert eng.weights["tok_emb"].devices() == {dev}
        assert eng._scope.get_var(K_PAGES_VAR).devices() == {dev}
    with srv:
        outs = [eng.submit([3, 5, 7], max_new_tokens=3).result(timeout=120)
                for eng in srv.replicas]
    assert all(o == outs[0] for o in outs)  # same tokens on every chip
    for eng, dev in zip(srv.replicas, want):  # state stayed where it was
        assert eng._scope.get_var(K_PAGES_VAR).devices() == {dev}
    # more replicas than devices wrap around; a single replica is
    # pinned like any other (one path, the one the chip smoke serves on)
    from paddle_tpu.serving.server import replica_places

    n = len(jax.devices())
    assert [p.device_id for p in replica_places(n + 1)] == \
        list(range(n)) + [0]
    assert [p.device_id for p in replica_places(1)] == [0]
    one = DecodeServer(model, weights, DecodeConfig(max_seq_len=64, slots=2))
    assert one.replicas[0]._scope.get_var(K_PAGES_VAR).committed


def test_disagg_server_spreads_prefill_and_decode_sets():
    """The disaggregated router's replicas get a device each, and a
    handoff between two of them is a device-to-device page copy that
    leaves the tokens bitwise those of a single engine."""
    import jax

    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.disagg import DisaggConfig, DisaggServer

    model, weights = _tiny_lm()
    cfg = DecodeConfig(max_seq_len=64, slots=2)
    srv = DisaggServer(model, weights, config=cfg, disagg=DisaggConfig(
        prefill_replicas=1, decode_replicas=1))
    assert [r.engine.device for r in srv.replicas] == jax.devices()[:2]
    prompt = list(range(1, 20))
    with srv:
        got = srv.submit(prompt, max_new_tokens=4, temperature=1.0,
                         seed=7).result(timeout=120)
    with DecodeEngine(model, weights, cfg) as eng:
        want = eng.submit(prompt, max_new_tokens=4, temperature=1.0,
                          seed=7).result(timeout=120)
    assert got == want


def test_disagg_handoff_over_expert_parallel_mesh():
    """Expert-parallel weights live on a mesh, so their replicas stay
    unpinned and a KV handoff must not commit the destination's pools to
    one device: the first handoff lands in a pool that is committed
    nowhere, the second in one the mesh-sharded step has written."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.decode import TransformerLM, shard_moe_weights
    from paddle_tpu.serving.disagg import DisaggConfig, DisaggServer
    from paddle_tpu.serving.server import replica_places

    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    model = TransformerLM(vocab_size=61, d_model=32, num_layers=2,
                          num_heads=2, max_seq_len=64, moe_experts=4,
                          moe_mesh=mesh)
    weights = shard_moe_weights(
        model.init_weights(jax.random.PRNGKey(0)), mesh)
    assert replica_places(2, model) == [None, None]
    cfg = DecodeConfig(max_seq_len=64, slots=2)
    prompts = [list(range(1, 20)), list(range(3, 30))]
    srv = DisaggServer(model, weights, config=cfg, disagg=DisaggConfig(
        prefill_replicas=1, decode_replicas=1))
    with srv:
        got = [srv.submit(p, max_new_tokens=4).result(timeout=120)
               for p in prompts]
    with DecodeEngine(model, weights, cfg) as eng:
        want = [eng.submit(p, max_new_tokens=4).result(timeout=120)
                for p in prompts]
    assert got == want


def test_compile_cache_is_placed_from_outside():
    """JAX_COMPILATION_CACHE_DIR in the environment: the program sets no
    directory at all.  Unset: one fixed path inside the checkout (never
    a temporary name, a pid or a time), on the chip only."""
    from paddle_tpu.framework import executor

    d = executor.default_compile_cache_dir
    assert d({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}, "tpu") is None
    assert d({}, "cpu") is None  # a process that selected the CPU
    path = d({}, "tpu")
    assert path == executor.COMPILE_CACHE_DIR == d({}, "tpu")
    assert path == os.path.join(ROOT, ".jax_compile_cache")
    ignored = open(os.path.join(ROOT, ".gitignore")).read().split()
    assert ".jax_compile_cache/" in ignored


def test_environment_cache_dir_is_left_untouched(tmp_path):
    """A fresh process with the variable set: building an Executor
    leaves jax's cache directory exactly what the environment said, and
    importing paddle_tpu creates nothing."""
    code = (
        "import os, jax, paddle_tpu as pt\n"
        "assert not os.path.exists(os.environ['JAX_COMPILATION_CACHE_DIR'])\n"
        "pt.Executor(pt.CPUPlace())\n"
        "print('DIR', jax.config.jax_compilation_cache_dir)\n")
    target = str(tmp_path / "from_outside")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=target,
               PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr
    assert f"DIR {target}" in p.stdout


def test_import_initialises_no_backend_and_creates_nothing(tmp_path):
    """`import paddle_tpu` touches no device, describes no topology and
    creates no directory (the six-worker test run depends on it: only
    one process at a time may load the TPU library)."""
    code = (
        "import os, sys\n"
        "before = set(os.listdir('.'))\n"
        "import paddle_tpu\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "assert not any('libtpu' in m for m in sys.modules), 'libtpu'\n"
        "assert set(os.listdir('.')) == before\n"
        "print('CLEAN')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True)
    assert p.returncode == 0 and "CLEAN" in p.stdout, p.stderr


def test_launcher_refuses_several_trainers_on_a_tpu_host(monkeypatch):
    """One process drives all local chips: N trainers that would each
    open every chip are refused with the reason, not left to hang."""
    from paddle_tpu.distributed import launch

    monkeypatch.setattr(launch, "tpu_present", lambda: True)
    with pytest.raises(SystemExit, match="one process at a time"):
        launch.launch(["--nproc_per_node", "2", "train.py"])
    # a job that selected the CPU opens no chip, whatever the host has
    monkeypatch.undo()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch.tpu_present() is False


def test_the_block_sweep_rehearses_on_the_cpu_and_times_nothing_there(
        tmp_path, monkeypatch):
    """``tools/sweep_decode_block.py`` (the numbers behind
    ``pages_per_block``): at ``--tiny`` sizes the kernel in the
    interpreter at a block the rule does not give and at the one it
    does, each against the reference, the copy-only kernel beside them,
    and the rule back in place afterwards; without ``--tiny`` it wants a
    TPU."""
    import json

    from paddle_tpu.ops import pallas_decode_attention as pda
    from tools import sweep_decode_block as sweep

    rule, out = pda.pages_per_block, tmp_path / "sweep.json"
    monkeypatch.setattr(sys, "argv", [
        "sweep", "--tiny", "--shapes", "mimo_global", "--blocks", "128,512",
        "--iters", "1", "--out", str(out)])
    sweep.main()
    with open(out) as f:
        lines = json.load(f)
    assert [(x["block"], x["rule"]) for x in lines] \
        == [(128, False), (512, True)]
    assert all(x["max_err"] < 1e-5 and x["copies_ms"] > 0 for x in lines)
    assert pda.pages_per_block is rule
    monkeypatch.setattr(sys, "argv", ["sweep"])
    with pytest.raises(SystemExit, match="a TPU or nothing"):
        sweep.main()


def test_the_prompt_flash_sweep_rehearses_on_the_cpu_and_times_nothing_there(
        tmp_path, monkeypatch, capsys):
    """``tools/sweep_prompt_flash.py`` (the numbers behind ``flash_rule``'s
    tiles at heads of 64 lanes, PR 60): at ``--tiny`` sizes the kernel in
    the interpreter at the tiles the rule gives and at another pair, V as
    it lies and padded to whole lanes, each against the plain blocks,
    with the keys its walk covers; the table's header is printed and no
    line holds a time; without ``--tiny`` it wants a TPU."""
    import json

    from paddle_tpu.ops import pallas_prompt_attention as ppa
    from tools import sweep_prompt_flash as sweep

    out = tmp_path / "sweep.json"
    monkeypatch.setattr(sys, "argv", [
        "sweep", "--tiny", "--shapes", "heads_of_64", "--rows", "64",
        "--keys", "128", "--out", str(out)])
    sweep.main()
    assert capsys.readouterr().out.startswith(
        "shape length form rows keys rule ms_a_call kernel_ms "
        "live_over_walked\n")
    with open(out) as f:
        lines = json.load(f)
    ruled = ppa.flash_rule(*sweep.TINY["heads_of_64"][:6])
    assert ruled == (256, 256)
    assert [(x["form"], x["length"], x["rows"], x["keys"], x["rule"])
            for x in lines] == [
        ("blocks", 256, 256, 256, False),
        ("flash", 130, 64, 128, False), ("flash", 256, 64, 128, False),
        ("flash", 130, 256, 256, True), ("flash", 256, 256, 256, True),
        ("flash_v128", 130, 64, 128, False),
        ("flash_v128", 256, 64, 128, False),
        ("flash_v128", 130, 256, 256, False),
        ("flash_v128", 256, 256, 256, False)]
    # bfloat16 K/V: the kernel's one bfloat16 term against the plain
    # form's float32 products
    assert all(x["rms_err"] < 4e-3 and "ms_a_call" not in x
               and "error" not in x for x in lines)
    # 130 tokens are three row blocks of 64: one, one and two key blocks
    assert lines[1]["keys_walked"] == 64 * 128 * 4
    assert lines[1]["live_over_walked"] == round(
        130 * 131 // 2 / (64 * 128 * 4), 4)
    monkeypatch.setattr(sys, "argv", ["sweep"])
    with pytest.raises(SystemExit, match="a TPU or nothing"):
        sweep.main()


def test_the_layout_sweep_rehearses_on_the_cpu_and_times_nothing_there(
        tmp_path, monkeypatch):
    """``tools/sweep_moe_layout.py`` (the numbers behind the grouped
    experts' pair layout): at ``--tiny`` sizes every form it keeps, the
    served one and the ones not taken, gives the five arrays of the
    layout as it was until PR 55 and says how many updates its scatter
    walks; no line holds a time; without ``--tiny`` it wants a TPU."""
    import json

    from tools import sweep_moe_layout as sweep

    out = tmp_path / "sweep.json"
    monkeypatch.setattr(sys, "argv", [
        "sweep", "--tiny", "--shapes", "all_held,odd", "--out", str(out)])
    sweep.main()
    with open(out) as f:
        lines = json.load(f)
    assert {x["form"] for x in lines} == set(sweep.FORMS)
    assert all(x["equal"] and "us_a_call" not in x for x in lines)
    updates = {(x["shape"], x["form"]): x["updates"] for x in lines}
    assert updates["all_held", "parent"] == 512 * 16
    assert updates["all_held", "served"] == 512 * 4
    assert updates["odd", "parent"] == 256 * 12
    assert updates["odd", "served"] == 256 * 8
    monkeypatch.setattr(sys, "argv", ["sweep"])
    with pytest.raises(SystemExit, match="a TPU or nothing"):
        sweep.main()


def test_the_kda_sweep_rehearses_on_the_cpu_and_times_nothing_there(
        tmp_path, monkeypatch):
    """``tools/sweep_kda_chunk.py`` (the numbers behind the channel-decay
    rule's chunk form, PR 58): at ``--tiny`` sizes the form that was
    (one token after another), the served chunk form at another
    sub-chunk and heads a grid step, and the XLA form not taken all give
    the outputs and the state of ``T = 1`` calls of the step's kernel,
    and the model's own call runs in the same loop;
    no line holds a time; the module's knobs are its own again
    afterwards; without ``--tiny`` it wants a TPU."""
    import json

    from paddle_tpu.ops import pallas_kda_chunk as chunked
    from tools import sweep_kda_chunk as sweep

    out, knobs = tmp_path / "sweep.json", (chunked.HEADS_A_STEP, chunked.SUB)
    monkeypatch.setattr(sys, "argv", [
        "sweep", "--tiny", "--tokens", "128", "--heads", "4", "--subs",
        "8", "--forms", "token,chunk,xla,layer", "--out", str(out)])
    sweep.main()
    with open(out) as f:
        lines = json.load(f)["lines"]
    assert [(x["form"], x["heads_a_step"], x["sub_chunk"]) for x in lines] \
        == [("token", None, None), ("chunk", 4, 8), ("xla", None, None),
            ("layer", 4, 8)]
    # the layer form (the model's own call) runs on projections of its
    # own: finite, compared with nothing
    assert all(x.get("max_err", 0.0) < 2e-5 and x["finite"]
               and "ms_a_layer" not in x and "error" not in x for x in lines)
    assert "max_err" not in lines[-1]
    assert not any(x["served"] for x in lines)
    assert (chunked.HEADS_A_STEP, chunked.SUB) == knobs
    assert sweep.served_group(sweep.SHAPES["kimi"]) == 256 \
        and sweep.served_group(sweep.SHAPES["solar512"]) == 128
    monkeypatch.setattr(sys, "argv", ["sweep"])
    with pytest.raises(SystemExit, match="a TPU or nothing"):
        sweep.main()


SERVING = os.path.join(ROOT, "paddle_tpu", "serving")
# what a file under ``serving/`` may import of ``serving/``: the arrows
# of ``ops/`` <- ``blocks.py`` <- ``mixers.py`` <- ``*_lm.py``
MODEL_FILES = sorted(f for f in os.listdir(SERVING) if f.endswith("_lm.py"))


def _serving_imports(name):
    """The modules of ``paddle_tpu.serving`` that ``serving/<name>``
    imports, anywhere in the file (the files import each other by
    ``from .x import`` alone: an absolute spelling fails here too)."""
    import ast

    with open(os.path.join(SERVING, name)) as f:
        source = f.read()
    assert "paddle_tpu.serving" not in source.split('"""', 2)[2]
    found = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.ImportFrom) and node.level == 1:
            found.update([node.module.split(".")[0]] if node.module
                         else [a.name for a in node.names])
    return found


@pytest.mark.parametrize("name", MODEL_FILES + ["blocks.py", "mixers.py"])
def test_the_served_models_library_has_its_arrows_one_way(name):
    """No model file imports another model file (a model's block changes
    in that model's file and moves that model's cell alone: PR 57 and
    PR 58, claimed on Kimi-Linear, moved Solar's by 8.5 % through a class
    that lived in Solar's file); what two models share is in
    ``blocks.py`` (stateless, nothing of ``serving/`` imported) or
    ``mixers.py`` (the stateful mixins, ``blocks`` alone), and no class
    borrows another model class's methods."""
    assert len(MODEL_FILES) == 11
    allowed = {"blocks.py": set(), "mixers.py": {"blocks"}}.get(
        name, {"blocks", "mixers"})
    assert _serving_imports(name) <= allowed, name
    with open(os.path.join(SERVING, name)) as f:
        assert not re.search(r"= \w+LM\._\w+", f.read())
