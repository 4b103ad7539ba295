"""Closed-loop traffic for a serving cell: N callers, each submits a
request, waits for its whole reply through ``on_token``, and submits
again at once.  Callers that wait for a reply (batch inference, agents)
make such a loop; a slow system receives less load.

Parameters (the workload file's ``traffic``): ``callers``,
``prompt_len`` [lo, hi] (log-uniform), ``reply_len`` [lo, hi] (uniform),
``pool`` (how many distinct (prompt, reply) sizes there are),
``stagger_s`` (callers start spread over this long).

Every seed gets the SAME pool of sizes - the quantiles of the two
distributions, paired by a fixed shuffle - in another order, with other
token ids: the seed changes which request meets which, not how much work
a window holds.  Token ids are uniform over the vocabulary, so no two
prompts share a prefix.

One client thread does all submitting; ``on_token`` (called on the
engine's thread) only stamps the clock, keeps the token, and hands a
finished caller back through a queue.
"""
import contextlib
import queue
import threading
import time

import numpy as np


def size_pool(params):
    """The fixed list of (prompt_len, reply_len): same for every seed."""
    n = int(params["pool"])
    q = (np.arange(n) + 0.5) / n
    p_lo, p_hi = params["prompt_len"]
    r_lo, r_hi = params["reply_len"]
    prompts = np.rint(np.exp(
        np.log(p_lo) + q * (np.log(p_hi) - np.log(p_lo)))).astype(int)
    replies = np.rint(r_lo + q * (r_hi - r_lo)).astype(int)
    pairing = np.random.RandomState(0).permutation(n)
    return [(int(p), int(replies[j])) for p, j in zip(prompts, pairing)]


def requests(params, vocab_size, seed):
    """An endless stream of (prompt token ids, max_new_tokens): the pool
    in a seeded order, reshuffled on every pass."""
    rng = np.random.RandomState(int(seed))
    pool = size_pool(params)
    while True:
        for i in rng.permutation(len(pool)):
            p_len, r_len = pool[i]
            yield rng.randint(0, vocab_size, p_len).tolist(), r_len


class Record:
    __slots__ = ("caller", "prompt_len", "max_new", "t_submit", "ts",
                 "tokens", "req", "error")

    def __init__(self, caller, prompt_len, max_new):
        self.caller, self.prompt_len, self.max_new = (
            caller, prompt_len, max_new)
        self.t_submit = None
        self.ts, self.tokens = [], []
        self.req = self.error = None


class Client:
    """The callers.  ``submit(prompt, max_new_tokens=, on_token=)`` is
    the server's; ``span`` wraps each submit in a benchmark span."""

    def __init__(self, submit, stream, params, span=None):
        self._submit, self._stream = submit, stream
        self._callers = int(params["callers"])
        self._stagger = float(params.get("stagger_s", 0.0))
        self._span = span
        self._done = queue.SimpleQueue()
        self._stop = threading.Event()
        self._live = {}
        self.records = []           # every request ever submitted
        self.submit_errors = []     # (time, repr) of refused submits
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-client")

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join()

    def _send(self, caller):
        prompt, max_new = next(self._stream)
        rec = Record(caller, len(prompt), max_new)
        done = self._done

        def on_token(tok, rec=rec):
            rec.ts.append(time.perf_counter())
            rec.tokens.append(tok)
            if len(rec.tokens) == rec.max_new:
                done.put(rec.caller)

        rec.t_submit = time.perf_counter()
        span = self._span("submit", caller=caller) if self._span \
            else contextlib.nullcontext()
        try:
            with span:
                rec.req = self._submit(prompt, max_new_tokens=max_new,
                                       on_token=on_token)
        except Exception as e:  # noqa: BLE001 - a refused submit is a
            rec.error = repr(e)  # failed request, and the loop goes on
            self.submit_errors.append((rec.t_submit, rec.error))
        self.records.append(rec)
        self._live[caller] = rec

    def _reap_failed(self):
        """A request the engine ended short of its reply has failed;
        its caller sends the next one."""
        for caller, rec in list(self._live.items()):
            failed = rec.error is not None or (
                rec.req.done() and len(rec.tokens) < rec.max_new)
            if failed:
                if rec.error is None:
                    rec.error = "ended after %d of %d tokens" % (
                        len(rec.tokens), rec.max_new)
                    rec.ts.append(time.perf_counter())
                self._send(caller)

    def _loop(self):
        t0 = time.perf_counter()
        due = [(t0 + i * self._stagger / max(self._callers, 1), i)
               for i in range(self._callers)]
        while not self._stop.is_set():
            now = time.perf_counter()
            while due and due[0][0] <= now:
                self._send(due.pop(0)[1])
            wait = 0.25 if not due else max(
                min(0.25, due[0][0] - now), 0.0)
            try:
                caller = self._done.get(timeout=wait)
            except queue.Empty:
                self._reap_failed()
                continue
            if self._stop.is_set():
                break
            self._send(caller)
