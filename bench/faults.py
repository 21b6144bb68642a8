"""Faults planted underneath the harness, to show that ``correct`` catches
them (``tests/test_correct.py`` on the CPU, ``tools/readings.py --fault``
on the chip).  Each is a context manager that breaks one place of the
system under test and restores it on exit."""
from __future__ import annotations

from contextlib import contextmanager


@contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextmanager
def state_unchanged():
    """A training call that returns its state unchanged."""
    from repro.core.engine import FedEngine
    with _patched(FedEngine, "run", lambda self, state, *a, **k: state):
        yield


@contextmanager
def half_batch():
    """Half of each local-update batch left out, the mean taken over the
    rest."""
    import repro.core.client as client
    full = client.xent_int_labels

    def half(logits, labels, mask=None):
        n = logits.shape[0] // 2
        return full(logits[:n], labels[:n])

    with _patched(client, "xent_int_labels", half):
        yield


@contextmanager
def token_altered():
    """Every served token altered where the server produces it."""
    from repro.serve.engine import ServeEngine
    emit = ServeEngine._emit

    def altered(self, slot, token, now):
        return emit(self, slot, (token + 1) % self.cfg.vocab, now)

    with _patched(ServeEngine, "_emit", altered):
        yield


@contextmanager
def cache_unchanged():
    """A decode step that returns its KV cache unchanged."""
    import repro.serve.engine as se
    step = se.model_decode_step

    def stale(cfg, params, cache, tok, pos):
        logits, _ = step(cfg, params, cache, tok, pos)
        return logits, cache

    with _patched(se, "model_decode_step", stale):
        yield


@contextmanager
def none():
    yield


FAULTS = {"none": none, "state_unchanged": state_unchanged,
          "half_batch": half_batch, "token_altered": token_altered,
          "cache_unchanged": cache_unchanged}
