"""Record ``data/tiny_engine_trace.xplane.pb``: a toy ``DecodeEngine`` on
the chip under a profiler session opened as ``run.py`` opens it, with the
program's own spans in it.  Two requests, three tokens each: one engine
iteration that admits and prefills both and steps once, one more that
only steps, and the admission that finds nothing left before the thread
goes idle.

    chiprun -- python benchmark/tests/record_engine_trace.py <out dir>

Run by hand when the spans change; ``test_engine_reader.py`` holds the
numbers read from the file that is there."""
import glob
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir):
    import jax
    import numpy as np

    from paddle_tpu.serving.decode import (DecodeConfig, DecodeEngine,
                                           TransformerLM)

    print(jax.devices())
    model = TransformerLM(vocab_size=512, d_model=256, num_heads=4,
                          num_layers=2, max_seq_len=128)
    weights = model.init_weights(jax.random.PRNGKey(0))
    eng = DecodeEngine(model, weights, DecodeConfig(
        slots=4, max_seq_len=128, page_size=16, prefix_cache=False)).start()
    rng = np.random.RandomState(0)

    def prompt(n):
        return rng.randint(0, 512, n).tolist()

    for n in (12, 30):                  # warm both prefill buckets + step
        eng.submit(prompt(n), max_new_tokens=3).result(timeout=600)
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        reqs = [eng.submit(prompt(n), max_new_tokens=3) for n in (12, 30)]
        for r in reqs:
            r.result(timeout=600)
        time.sleep(0.002)
    jax.profiler.stop_trace()
    eng.stop()
    (path,) = glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "tiny_engine_trace.xplane.pb")
    shutil.copy(path, out)
    print(out, os.path.getsize(out), [r.trace.trace_id for r in reqs])


if __name__ == "__main__":
    main(sys.argv[1])
