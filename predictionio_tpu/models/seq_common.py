"""What the packed-stream decoders share (models/looped_lm.py,
models/latent_moe_lm.py, models/hybrid_ssm_lm.py): the float32 RMSNorm
and the two layouts a trainer or ``pio eval`` turns left-padded history
rows into. One copy, so that a change to either reaches all three."""

from __future__ import annotations

__all__ = ["rms_norm", "rows_as_streams", "rows_to_stream"]


def rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rows_as_streams(seqs):
    """Left-padded histories [B, L] as a stream of B rows: segment 1 for
    the real events (0 for the pads, which only see each other), position
    = index among the real events."""
    import jax.numpy as jnp

    real = seqs > 0
    pos = jnp.maximum(jnp.cumsum(real, axis=1) - 1, 0)
    return seqs, real.astype(jnp.int32), pos.astype(jnp.int32)


def rows_to_stream(seqs):
    """Left-padded histories [B, L] as ONE stream of B x L tokens: a
    segment a row (0 for the pads), position = index among the row's real
    events."""
    import jax.numpy as jnp

    B, L = seqs.shape
    real = seqs > 0
    pos = jnp.maximum(jnp.cumsum(real, axis=1) - 1, 0)
    seg = jnp.where(real, jnp.arange(1, B + 1)[:, None], 0)
    return (seqs.reshape(B * L).astype(jnp.int32),
            seg.reshape(B * L).astype(jnp.int32),
            pos.reshape(B * L).astype(jnp.int32))
