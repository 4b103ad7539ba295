"""What the four served models' test files share of one check (no test
is collected here): a request served as the engine serves it, where the
whole-prompt prefill names the ONE row it reads (``attend.read_row``)
and the model hands back ``[1, V]``, against the same request with the
name withheld, where the model forms every row's logits and the engine
picks the row itself (the form every prefill had until PR 62)."""
import numpy as np

from paddle_tpu.serving import decode


class _NoRowNamed(decode._Mixers):
    """``_Mixers`` as the engine builds it, ``read_row`` withheld."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **dict(kw, read_row=None))


def serve(tests, prompt, n_new, name_the_row, monkeypatch):
    """``prompt`` through ``tests.engine(tests.make_model())``, greedy ->
    (tokens, recorded logits ``[n_new, V]``, what each traced program's
    ``forward`` was handed and gave: ``(attend.prompt, a row was named,
    logits.shape)``)."""
    import jax

    model = tests.make_model()
    weights = model.init_weights(jax.random.PRNGKey(3))
    seen, forward = [], model.forward

    def spy(w, tokens, positions, cache, attend):
        logits, cache = forward(w, tokens, positions, cache, attend)
        seen.append((attend.prompt, attend.read_row is not None,
                     tuple(logits.shape)))
        return logits, cache

    model.forward = spy
    with monkeypatch.context() as patch, tests.engine(model, weights) as eng:
        if not name_the_row:
            patch.setattr(decode, "_Mixers", _NoRowNamed)
        req = eng.submit(prompt, max_new_tokens=n_new, record_logits=True)
        tokens = req.result(timeout=280)
    return tokens, np.stack(req.logits_trace), seen


def the_read_row_is_the_every_row_forms(tests, length, bucket, monkeypatch):
    """A prompt of ``length`` tokens in a ``bucket`` of rows: the served
    request's prefill hands back one row, the step every slot's; tokens
    and recorded logits are the every-row form's."""
    model = tests.make_model()
    prompt = np.random.RandomState(length).randint(
        0, model.vocab_size, length).tolist()
    toks, logits, seen = serve(tests, prompt, 3, True, monkeypatch)
    slots = max(shape[0] for prompt_, _, shape in seen if not prompt_)
    assert sorted(set(seen)) == [
        (False, False, (slots, model.vocab_size)),
        (True, True, (1, model.vocab_size))], seen
    toks_all, logits_all, seen_all = serve(tests, prompt, 3, False,
                                           monkeypatch)
    # the row read lies INSIDE the bucket where the prompt is shorter
    assert sorted(set(seen_all)) == [
        (False, False, (slots, model.vocab_size)),
        (True, False, (bucket, model.vocab_size))], seen_all
    assert toks == toks_all
    np.testing.assert_allclose(logits, logits_all, rtol=2e-5, atol=2e-6)
