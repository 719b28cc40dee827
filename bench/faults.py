"""Faults a served cell can have, planted in a built server: the check
that decides ``correct`` has to come out false under each.  Used by the
tests at smoke size and by ``bench/tools/control.py`` at a cell's size."""
from __future__ import annotations

__all__ = ["FAULTS"]


def alter_tokens(server) -> None:
    """A token altered where it is produced: every request's third
    emission from a decode step is replaced by the next vocabulary id."""
    core, vocab = server.core, server.arch.vocab_size
    step = core.step
    counts = {}

    def altered(emit):
        def e(req, tok, done):
            counts[req.rid] = counts.get(req.rid, 0) + 1
            if counts[req.rid] == 3:
                tok = (tok + 1) % vocab
            emit(req, tok, done)

        return step(e)

    core.step = altered


def stale_state(server) -> None:
    """The decode step returns its cache unchanged: the state (SSM, conv,
    KV ring) never advances past the prompt."""
    import jax

    core = server.core
    decode = core._decode

    def stale(p, t, pos, c):
        logits, _ = decode(p, t, pos, jax.tree.map(lambda a: a + 0, c))
        return logits, c

    core._decode = stale


FAULTS = {"token_altered": alter_tokens, "state_unchanged": stale_state}
