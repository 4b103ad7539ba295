"""Per-layer readers of the training cells.  A reader takes the run's
``sources`` and its metric file's ``params`` and returns a number, or
None where there is nothing to read."""


def enqueue_ms_per_call(sources, params):
    """Executor layer: ``run_steps`` call -> return, before
    ``block_until_ready``, on the benchmark's own clock; mean over the
    window's calls."""
    calls = sources.get("train", {}).get("enqueue_s")
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)


def device_busy_ms_per_step(sources, params):
    """Model step: union of device-op intervals in the window over the
    steps it ran (mean over chips)."""
    trace, train = sources.get("trace"), sources.get("train")
    if not trace or not train or not train["steps"]:
        return None
    return 1e3 * trace["busy_s"] / train["steps"]


def step_roofline(sources, params):
    """Kernels, training: the least time the chip could take for the
    step's forward+backward FLOPs at its bf16 peak (compute-bound), over
    the device-busy time per step; in %."""
    busy_ms = device_busy_ms_per_step(sources, params)
    if not busy_ms:
        return None
    train = sources["train"]
    least_s = train["flops_per_step_per_chip"] / (
        sources["peaks"]["bf16_tflops"] * 1e12)
    return 100.0 * least_s / (busy_ms * 1e-3)
