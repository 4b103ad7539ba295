"""The test-only path of the benchmark's kinds: a ``Bench`` built on the
CPU's devices around a cell whose widths are cut HERE, in the tests,
never in the benchmark's files, handed to the kind's ``run`` directly.
The command itself has no option that lets it run without a chip."""
import copy
import os

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {
    "bert_base": {"model": dict(vocab_size=100, hidden=32, n_layers=2,
                                n_heads=2, ffn_size=64, seq_len=16,
                                max_preds_per_seq=4, dropout_prob=0.1)},
    "gpt2_medium": {"model": dict(vocab_size=97, n_embd=32, n_layer=2,
                                  n_head=2, n_positions=128, ffn_dim=64),
                    "serving": dict(slots=4, max_seq_len=128,
                                    use_pallas="always", interpret=True)},
}
CPU_PEAKS = {"bf16_tflops": 1.0, "hbm_gbps": 1.0, "hbm_gb": 1.0,
             "source": "a made-up part: rehearsal only, never reported"}


def rehearse(cell_name, seconds, trace=False, spec_overrides=None,
             n_devices=1, root=ROOT, seed=2**31 + 12345):
    """Run one cell's kind on the CPU at a tiny size; returns (bench,
    result).  Widths are cut HERE, in the test, never in the files."""
    import time

    import jax

    cell = bench_run.resolve_cell(root, cell_name)
    cell = dict(cell, config=copy.deepcopy(cell["config"]),
                spec=copy.deepcopy(cell["spec"]))
    for key, val in TINY.get(cell["workload"]["config"], {}).items():
        cell["config"][key].update(val)
    for key, val in (spec_overrides or {}).items():
        if isinstance(val, dict):
            cell["spec"].setdefault(key, {}).update(val)
        else:
            cell["spec"][key] = val
    cell["chips"] = n_devices
    bench = bench_run.Bench(cell, seed, seconds, trace,
                            jax.devices()[:n_devices], CPU_PEAKS,
                            time.perf_counter())
    bench.listen()
    return bench, cell["kind"].run(bench)
