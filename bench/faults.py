"""Faults planted under the timed path, to see ``correct`` come out false:
each is an ``engine_hook`` for ``bench.harness.Run`` that replaces the
engine's compiled decode step by a broken one.  The CPU tests run them at a
small size; ``calibrate.py --fault`` runs one at a cell's own size on the
chip.  The benchmark's own runs never plant one."""
import jax.numpy as jnp

__all__ = ["FAULTS"]


def _wrap_decode(change):
    """A hook whose decode step returns ``change(logits, old_cache,
    new_cache)``."""
    def hook(eng):
        decode = eng._decode

        def broken(params, tok, cache, *rest):
            logits, new = decode(params, tok, cache, *rest)
            return change(logits, cache, new)
        eng._decode = broken
    return hook


def _half_batch(logits, old, new):
    # the second half of the batch left out: its rows get the first half's
    b = logits.shape[0] // 2
    return jnp.concatenate([logits[:b], logits[:b]]), new


FAULTS = {
    # the step returns its state unchanged: decode writes no KV
    "state_unchanged": _wrap_decode(lambda lg, old, new: (lg, old)),
    "half_batch_left_out": _wrap_decode(_half_batch),
    # a token altered where it is produced: every argmax moves by one
    "token_altered": _wrap_decode(
        lambda lg, old, new: (jnp.roll(lg, 1, axis=-1), new)),
}
