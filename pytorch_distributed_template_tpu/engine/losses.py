"""Loss functions.

Reference: ``model/loss.py`` — a single ``nll_loss`` over log-probabilities
(/root/reference/model/loss.py:4-5). Here losses are **per-example** pure
functions ``(output, target) -> [B]``; the engine applies the padding mask
and reduces. That single convention makes every loss exact under the
duplicate-padded final batches the sampler produces (SURVEY.md §7 hard-part
(c)) and lets metrics/losses share reduction machinery inside jit.
"""
from __future__ import annotations

import contextlib
import contextvars
import logging

import jax
import jax.numpy as jnp
import optax

from ..config.registry import LOSSES
from ..models.remat_policy import HEADROOM_BYTES, token_shards
from ..observability.trace import say_once

logger = logging.getLogger(__name__)


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


# Rows (positions x sequences on ONE device) that a slice of the fused head
# and loss is made of, where the sequence is long enough. The loop that
# makes the gradients (the forward's in a train step, the backward's for a
# bare per-example gradient) adds every slice's share into the head's whole
# weight gradient, [D, V] in the compute dtype: read once and written once a
# turn, 2 x 2 D V bytes, beside a matmul of 2 x rows x D x V operations.
# The two take the same time at rows = 2 x peak / bandwidth: 481 on a v5e
# (197 TFLOP/s, 819 GB/s), and of that order on the v4, v5p and v6e by
# their published figures. At four times that, rewriting the accumulator
# is under a quarter of the matmul (and the logits pass's re-read of the
# weight an eighth). A constant of the arithmetic, not a knob: nothing
# reads a table or a device.
SLICE_ROWS = 2048

_step_mesh: contextvars.ContextVar = contextvars.ContextVar(
    "head_loss_step_mesh", default=None)


@contextlib.contextmanager
def step_mesh(mesh):
    """The step's word to the fused loss, around its trace: the batch it
    traces is spread over ``mesh`` (``engine/steps.py`` sets it for the
    train and the eval step). Under ``jit`` the loss sees the global
    ``[B, T, D]`` and cannot know; called outside a step it reckons the
    whole batch on one device."""
    token = _step_mesh.set(mesh)
    try:
        yield
    finally:
        _step_mesh.reset(token)


def slice_positions(sequences: int, chunk: int, seq_len: int,
                    vocab: int) -> int:
    """Positions of every sequence in one slice of the fused head and loss,
    for ``sequences`` of ``seq_len`` positions on one device.

    ``chunk`` is the floor. A slice takes as many positions as bring the
    device's sequences to ``SLICE_ROWS`` rows, rounded up to a
    multiple of ``chunk``, and never more than ``seq_len`` positions
    padded to one; then halved, not below ``chunk``, while the slice's
    float32 logits on a device exceed half of the checkpoint policy's
    ``HEADROOM_BYTES`` (models/remat_policy.py leaves that room free and
    reckons nothing of the loss's slices, so they have to fit in it: the
    float32 logits and, where the gradients are made in the same turn,
    their gradient in the compute dtype beside them)."""
    def up(n):
        return -(-n // chunk) * chunk

    positions = min(up(-(-SLICE_ROWS // sequences)), up(seq_len))
    while (positions > chunk and
           sequences * positions * vocab * 4 > HEADROOM_BYTES // 2):
        positions = up(positions // 2)
    return positions


def _say_slice(sequences, positions, turns, vocab, chunk, gradients):
    """The choice, once a process and distinct choice: a log line and a
    zero-length span, as ``remat/policy`` has. ``gradients`` says which
    loop makes the head's two gradients: the ``"forward"`` one (the summed
    form under differentiation) or the ``"backward"`` one (the per-example
    form, if anything differentiates it)."""
    rows = sequences * positions
    record = dict(rows_per_device=rows, positions=positions, turns=turns,
                  slice_bytes=rows * vocab * 4, floor_positions=chunk,
                  gradients=gradients)
    say_once(
        logger, "head_loss/slice", record,
        "head_loss/slice: %d rows on a device a turn (%d positions x %d "
        "sequences, the floor is %d positions), %d turns, %.1f MB of "
        "float32 logits a slice, gradients made in the %s loop", rows,
        positions, sequences, chunk, turns, record["slice_bytes"] / 1e6,
        gradients)


def _slice_nll(hc, w, lc):
    """One slice, logits to per-token loss: the body both entrances of the
    fused loss share. Operands in the compute dtype, logits, softmax and
    loss in float32 (``optax.softmax_cross_entropy_with_integer_labels``,
    spelled out for the log-normalizer). Returns ``(loss, logits,
    log-normalizer)``."""
    logits = (hc @ w).astype(jnp.float32)           # [..., positions, V]
    label = jnp.take_along_axis(logits, lc[..., None], axis=-1).take(
        0, axis=-1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse - label, logits, lse


@LOSSES.register("fused_lm_cross_entropy")
def fused_lm_cross_entropy(chunk: int = 256):
    """FACTORY loss: next-token CE fused with the LM head, in slices.

    Pairs with a model built with ``fused_head: true`` (models/transformer
    TransformerLM): ``output`` is ``(hidden [B,T,D], head_w [D,V])`` and
    the [B, T, V] logits tensor NEVER materializes — a ``lax.scan`` over
    slices of the sequence computes each slice's logits and its CE, so
    peak HBM holds one slice's logits instead of the full T. At GPT-2
    vocab (50257) and long T this is the dominant activation saved.

    One algorithm with two entrances that share the slice's body
    (``_slice_nll``):

    - ``loss(output, target) -> [B]``, per example, as every loss here. A
      gradient through it runs the loop again in the backward
      (``jax.checkpoint`` on the body recomputes each slice's logits): a
      second ``rows x D x V`` pass, because the per-example cotangent is
      not known before the backward. The eval step, the metrics, mixup
      and ``models/pipelined.py`` enter here.
    - ``loss.summed(output, target, weights) -> (sum_b weights[b] *
      per_example[b], per_example)``, for a caller that only sums (the
      train step: ``weights`` is its ``batch["mask"]``). The cotangent of
      every example is then ``weights[b]`` times one scalar, known before
      the loop starts, so under differentiation (a ``jax.custom_vjp``)
      each turn makes the slice's logits, its loss, the softmax's gradient
      ``weights[b] * valid / (T-1) * (softmax - onehot)`` in the compute
      dtype and at once both of the head's gradients from it: three
      matmul passes a step where the other entrance runs four, and no
      loop left in the backward, which multiplies the two by the incoming
      scalar. What is kept from forward to backward is the hidden state's
      gradient in place of the hidden state. ``per_example`` comes back
      as a value nothing differentiates. Not under differentiation it is
      the first entrance and a weighted sum.

    ``chunk`` is a FLOOR, in positions of one sequence. The slice itself
    is reckoned in rows on one device (``slice_positions``): at least
    ``chunk`` positions, and as many more, in multiples of ``chunk``, as
    bring the sequences a device holds to ``SLICE_ROWS`` rows, under a
    cap on the slice's bytes. A step that runs one or two long sequences
    a device would otherwise rewrite the head's whole weight gradient
    once per ``chunk`` rows, which costs more than their matmul. The
    choice, and which loop makes the gradients, is one ``head_loss/slice``
    log line and span a process.

    Numerically identical to ``lm_cross_entropy`` on the same params
    (same shift, per-sequence mean) up to float reassociation.
    """
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")

    def slices(output, target, gradients):
        """The shifted pair cut into scan-ready slices."""
        h, w = output                       # [B, T, D], [D, V]
        b, tm1 = h.shape[0], h.shape[1] - 1
        on_device = b // token_shards(_step_mesh.get(), b, h.shape[1])
        positions = slice_positions(on_device, chunk, tm1, w.shape[1])
        h_c, l_c, v_c = chunk_shifted_sequence(
            h[:, :-1], target[:, 1:], positions
        )
        _say_slice(on_device, positions, h_c.shape[0], w.shape[1], chunk,
                   gradients)
        return h_c, l_c, v_c

    @jax.named_scope("head_loss")   # the trace's head-and-loss share
    def loss(output, target):
        h, w = output
        b, tm1 = h.shape[0], h.shape[1] - 1
        h_c, l_c, v_c = slices(output, target, "backward")
        if b == 1:
            # a batch of one is folded away around the softmax (the sum
            # below broadcasts it back): jnp.take_along_axis reads a
            # dimension of size one as a window, not as a batch, and XLA
            # then transposes the label's gather into a scatter over the
            # slice's whole logits, where it otherwise fuses a one-hot
            # select into the matmuls' operands (10 ms a step at
            # 2048 x 32000)
            h_c, l_c = h_c[:, 0], l_c[:, 0]

        @jax.checkpoint
        def body(carry, inp):
            hc, lc, vc = inp
            tok = _slice_nll(hc, w, lc)[0]              # [B, positions]
            return carry + jnp.sum(tok * vc[None, :], axis=-1), None

        total, _ = jax.lax.scan(
            body, jnp.zeros((b,), jnp.float32), (h_c, l_c, v_c)
        )
        return total / tm1

    @jax.custom_vjp
    def summed(output, target, weights):
        per_ex = loss(output, target)
        return jnp.sum(per_ex * weights), per_ex

    @jax.named_scope("head_loss")
    def summed_fwd(output, target, weights):
        h, w = output
        b, t, d = h.shape
        vocab = w.shape[1]
        h_c, l_c, v_c = slices(output, target, "forward")
        # the cotangent of a token's loss, but for the incoming scalar
        per_token = weights.astype(jnp.float32)[:, None] / (t - 1)  # [B, 1]
        compute = jnp.result_type(h.dtype, w.dtype)

        def body(carry, inp):
            total, dw = carry
            hc, lc, vc = inp
            # every sequence's positions as rows of one matrix, [rows, .]
            # (a batch of one is folded away with them): plain matmuls
            # for all three passes
            hc, lc = hc.reshape(-1, d), lc.reshape(-1)
            tok, logits, lse = _slice_nll(hc, w, lc)
            ct = (per_token * vc[None, :]).reshape(-1)
            soft = jnp.exp(logits - lse[:, None])
            hit = lc[:, None] == jnp.arange(vocab, dtype=lc.dtype)
            # rounded where autodiff rounds it: behind the float32 logits
            dlogits = (jnp.where(hit, soft - 1.0, soft)
                       * ct[:, None]).astype(compute)
            dh = (dlogits @ w.T).astype(h.dtype)
            # added in float32 and rounded once a turn, as the compiler
            # fuses the add into the matmul. Summed over the whole batch:
            # where the batch is spread over chips the partitioner keeps
            # each chip's partial sum through the loop and crosses once
            # behind it (tests/test_chip_compile_head_loss.py reads that)
            dw = (dw + jnp.einsum(
                "rd,rv->dv", hc, dlogits,
                preferred_element_type=jnp.float32)).astype(dw.dtype)
            total = total + jnp.sum(
                tok.reshape(b, -1) * vc[None, :], axis=-1)
            return (total, dw), dh

        (total, dw), dh_c = jax.lax.scan(
            body, (jnp.zeros((b,), jnp.float32), jnp.zeros_like(w)),
            (h_c, l_c, v_c))
        # back from slices: [n, B x positions, D] -> [B, T, D], the padded
        # tail dropped and the last position, which predicts nothing, zero
        dh = jnp.moveaxis(dh_c.reshape(len(v_c), b, -1, d), 0, 1)
        dh = jnp.pad(dh.reshape(b, -1, d)[:, :t - 1],
                     ((0, 0), (0, 1), (0, 0)))
        per_ex = total / (t - 1)
        return (jnp.sum(per_ex * weights), per_ex), (dh, dw, per_ex)

    @jax.named_scope("head_loss")
    def summed_bwd(kept, cotangents):
        dh, dw, per_ex = kept
        g = cotangents[0]       # per_example's own is dropped: see above

        def times_g(x):
            return (x.astype(jnp.float32) * g).astype(x.dtype)

        return (times_g(dh), times_g(dw)), None, g * per_ex

    summed.defvjp(summed_fwd, summed_bwd)
    loss.summed = summed
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
