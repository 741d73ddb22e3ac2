"""Loss functions.

Reference: ``model/loss.py`` — a single ``nll_loss`` over log-probabilities
(/root/reference/model/loss.py:4-5). Here losses are **per-example** pure
functions ``(output, target) -> [B]``; the engine applies the padding mask
and reduces. That single convention makes every loss exact under the
duplicate-padded final batches the sampler produces (SURVEY.md §7 hard-part
(c)) and lets metrics/losses share reduction machinery inside jit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from ..config.registry import LOSSES


@LOSSES.register("nll_loss")
def nll_loss(output, target):
    """Negative log-likelihood over log-probability outputs (reference
    parity: the model ends in log_softmax)."""
    return -jnp.take_along_axis(output, target[:, None], axis=-1)[:, 0]


@LOSSES.register("cross_entropy")
def cross_entropy(output, target):
    """Softmax cross-entropy over raw logits."""
    return optax.softmax_cross_entropy_with_integer_labels(output, target)


@LOSSES.register("lm_cross_entropy")
def lm_cross_entropy(output, target):
    """Next-token LM loss: output [B, T, V] logits, target [B, T] tokens.

    Shifts internally (predict token t+1 from position t) and returns a
    per-sequence mean so the engine's per-example mask applies unchanged.
    """
    logits = output[:, :-1]
    labels = target[:, 1:]
    tok = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    return tok.mean(axis=-1)


@LOSSES.register("mlm_cross_entropy")
def mlm_cross_entropy(output, target):
    """Masked-LM loss for the BERT family (models/bert.py): ``output``
    is the model's ``(logits [B,T,V], mask [B,T])`` pair — the mask
    marks the positions the model corrupted in-graph — and ``target``
    is the ORIGINAL token stream. Per-example mean cross entropy over
    the masked positions only (unmasked positions would let the model
    score by copying its input)."""
    logits, sel = output
    tok = optax.softmax_cross_entropy_with_integer_labels(logits, target)
    denom = jnp.maximum(sel.sum(axis=-1), 1.0)
    return (tok * sel).sum(axis=-1) / denom


@LOSSES.register("mse_loss")
def mse_loss(output, target):
    return jnp.mean((output - target) ** 2, axis=tuple(range(1, output.ndim)))


@LOSSES.register("smooth_cross_entropy")
def smooth_cross_entropy(smoothing: float = 0.1):
    """FACTORY loss (dict-form config): label-smoothed softmax CE.

    Config: ``"loss": {"type": "smooth_cross_entropy",
    "args": {"smoothing": 0.1}}`` — the dict form is this framework's
    extension over the reference's name-only loss lookup
    (/root/reference/train.py:37); see :func:`resolve_loss`.
    """
    if not 0.0 <= smoothing < 1.0:
        raise ValueError(f"smoothing must be in [0, 1), got {smoothing}")

    def loss(output, target):
        n = output.shape[-1]
        onehot = jax.nn.one_hot(target, n, dtype=output.dtype)
        soft = onehot * (1.0 - smoothing) + smoothing / n
        return optax.softmax_cross_entropy(output, soft)

    return loss


smooth_cross_entropy._loss_factory = True  # dict-form config required


def chunk_shifted_sequence(h, labels, chunk: int, pad_label: int = 0):
    """Split an already-shifted (hidden, labels) pair into scan-ready
    chunk-leading arrays for the fused-head consumers (the chunked loss
    below and engine/metrics.lm_token_accuracy).

    h: [B, T-1, D]; labels: [B, T-1]. Returns ``(h_c [n, B, chunk, D],
    l_c [n, B, chunk], valid [n, chunk])`` where trailing padding rows are
    marked invalid and labels padded with ``pad_label``.
    """
    b, tm1, d = h.shape
    n_chunks = -(-tm1 // chunk)
    t_pad = n_chunks * chunk
    if t_pad != tm1:
        h = jnp.pad(h, ((0, 0), (0, t_pad - tm1), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, t_pad - tm1)),
                         constant_values=pad_label)
    h_c = jnp.moveaxis(h.reshape(b, n_chunks, chunk, d), 1, 0)
    l_c = jnp.moveaxis(labels.reshape(b, n_chunks, chunk), 1, 0)
    valid = (
        (jnp.arange(t_pad) < tm1).astype(jnp.float32)
        .reshape(n_chunks, chunk)
    )
    return h_c, l_c, valid


@LOSSES.register("fused_lm_cross_entropy")
def fused_lm_cross_entropy(chunk: int = 256):
    """FACTORY loss: next-token CE fused with the LM head, sequence-chunked.

    Pairs with a model built with ``fused_head: true`` (models/transformer
    TransformerLM): ``output`` is ``(hidden [B,T,D], head_w [D,V])`` and
    the [B, T, V] logits tensor NEVER materializes — a ``lax.scan`` over
    ``chunk``-token slices computes each slice's logits, its CE, and (via
    ``jax.checkpoint`` on the body) recomputes them in backward, so peak
    HBM holds one [B, chunk, V] slice instead of the full T. At GPT-2
    vocab (50257) and long T this is the dominant activation saved.
    Numerically identical to ``lm_cross_entropy`` on the same params
    (same shift, per-sequence mean) up to float reassociation.
    """
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")

    @jax.named_scope("head_loss")   # the trace's head-and-loss share
    def loss(output, target):
        h, w = output                       # [B, T, D], [D, V]
        tm1 = h.shape[1] - 1
        b = h.shape[0]
        h_c, l_c, v_c = chunk_shifted_sequence(
            h[:, :-1], target[:, 1:], chunk
        )

        @jax.checkpoint
        def body(carry, inp):
            hc, lc, vc = inp
            logits = (hc @ w).astype(jnp.float32)       # [B, chunk, V]
            tok = optax.softmax_cross_entropy_with_integer_labels(
                logits, lc
            )
            return carry + jnp.sum(tok * vc[None, :], axis=-1), None

        total, _ = jax.lax.scan(
            body, jnp.zeros((b,), jnp.float32), (h_c, l_c, v_c)
        )
        return total / tm1

    return loss


fused_lm_cross_entropy._loss_factory = True


def resolve_loss(loss_cfg):
    """Resolve the config ``loss`` entry to a per-example callable.

    A plain string keeps the reference's semantics (name lookup,
    train.py:37). A ``{"type", "args"}`` dict treats the registered object
    as a factory called with ``args`` — how parameterized losses (label
    smoothing) stay expressible without breaking the name-only contract.
    Form/kind mismatches raise HERE, at config-resolve time, instead of as
    an opaque arity error inside the first jit trace.
    """
    if isinstance(loss_cfg, str):
        loss = LOSSES.get(loss_cfg)
        if getattr(loss, "_loss_factory", False):
            raise ValueError(
                f"loss '{loss_cfg}' is parameterized; use the dict form "
                f'{{"type": "{loss_cfg}", "args": {{...}}}}'
            )
        return loss
    factory = LOSSES.get(loss_cfg["type"])
    if not getattr(factory, "_loss_factory", False):
        raise ValueError(
            f"loss '{loss_cfg['type']}' takes no args; use the string form "
            f'"loss": "{loss_cfg["type"]}"'
        )
    return factory(**dict(loss_cfg.get("args", {})))
