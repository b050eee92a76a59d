"""Fused multi-layer RNN operator.

TPU-native equivalent of the reference's fused RNN op
(src/operator/rnn-inl.h RNNParam; GPU path src/operator/cudnn_rnn-inl.h:152
cudnnRNNForwardTraining): modes rnn_relu / rnn_tanh / lstm / gru,
multi-layer, bidirectional, inter-layer dropout.

Design: one ``lax.scan`` over time per layer — the h2h matmul stays on the
MXU every step, XLA pipelines the scan; no per-step Python. Gate math
matches the reference cell definitions exactly (rnn_cell.py LSTMCell/GRUCell
slicing order: LSTM [i, f, c, o], GRU [r, z, n]) so fused and unrolled paths
are numerically interchangeable, as in the reference.

Input layout TNC (seq, batch, feature) like the reference op; weights arrive
as separate i2h/h2h weight/bias arrays per layer+direction in the same order
the reference packs its flat parameter blob (rnn-inl.h):
  for layer in layers: for dir in dirs: W_i2h, W_h2h
  then            : for layer in layers: for dir in dirs: b_i2h, b_h2h
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .nn import inverted_dropout
from .registry import register

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def _cell_step(mode):
    if mode == "rnn_relu":
        def step(x_proj, h, c, w_hh, b_hh):
            new_h = jax.nn.relu(x_proj + h @ w_hh.T + b_hh)
            return new_h, c
    elif mode == "rnn_tanh":
        def step(x_proj, h, c, w_hh, b_hh):
            new_h = jnp.tanh(x_proj + h @ w_hh.T + b_hh)
            return new_h, c
    elif mode == "lstm":
        def step(x_proj, h, c, w_hh, b_hh):
            gates = x_proj + h @ w_hh.T + b_hh
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            g = jnp.tanh(g)
            o = jax.nn.sigmoid(o)
            new_c = f * c + i * g
            new_h = o * jnp.tanh(new_c)
            return new_h, new_c
    elif mode == "gru":
        def step(x_proj, h, c, w_hh, b_hh):
            hp = h @ w_hh.T + b_hh
            xr, xz, xn = jnp.split(x_proj, 3, axis=-1)
            hr, hz, hn = jnp.split(hp, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            new_h = (1 - z) * n + z * h
            return new_h, c
    else:
        raise ValueError("unknown RNN mode %r" % mode)
    return step


def _layer_scan(x, h0, c0, w_ih, w_hh, b_ih, b_hh, mode, reverse=False):
    """Run one direction of one layer over the full sequence.

    The i2h projection for ALL timesteps is one big matmul (seq*batch, in) ×
    (in, gates*H) — maximal MXU utilization; the scan carries only the h2h
    recurrence."""
    step = _cell_step(mode)
    x_proj = x @ w_ih.T + b_ih            # (T, N, gates*H)

    def body(carry, xp):
        h, c = carry
        new_h, new_c = step(xp, h, c, w_hh, b_hh)
        return (new_h, new_c), new_h

    (hT, cT), ys = lax.scan(body, (h0, c0), x_proj, reverse=reverse)
    return ys, hT, cT


def _rnn_argnames(p):
    """Named inputs in the op's positional order (data, states, then
    layer-major/dir-inner weight+bias arrays — rnn-inl.h packing order)."""
    mode = p.get("mode", "lstm")
    layers = int(p.get("num_layers", 1))
    dirs = 2 if p.get("bidirectional") else 1
    names = ["data", "state"] + (["state_cell"] if mode == "lstm" else [])
    prefixes = ["%s%d" % ("lr"[d], l) for l in range(layers)
                for d in range(dirs)]
    for pre in prefixes:
        names += ["%s_i2h_weight" % pre, "%s_h2h_weight" % pre]
    for pre in prefixes:
        names += ["%s_i2h_bias" % pre, "%s_h2h_bias" % pre]
    return names


def _rnn_param_shapes(data_shape, p):
    """Back-fill weight shapes from the TNC data shape (ref: rnn-inl.h
    RNNParam inferring the fused blob size)."""
    mode = p.get("mode", "lstm")
    gates = _GATES[mode]
    h = int(p.get("state_size", 0))
    layers = int(p.get("num_layers", 1))
    dirs = 2 if p.get("bidirectional") else 1
    c = data_shape[2]
    shapes = {}
    for l in range(layers):
        in_dim = c if l == 0 else dirs * h
        for d in range(dirs):
            pre = "%s%d" % ("lr"[d], l)
            shapes["%s_i2h_weight" % pre] = (gates * h, in_dim)
            shapes["%s_h2h_weight" % pre] = (gates * h, h)
            shapes["%s_i2h_bias" % pre] = (gates * h,)
            shapes["%s_h2h_bias" % pre] = (gates * h,)
    n_states = layers * dirs
    shapes["state"] = (n_states, data_shape[1], h)
    shapes["state_cell"] = (n_states, data_shape[1], h)
    return shapes


@register("RNN", num_inputs=None, needs_rng=True, takes_is_train=True,
          num_outputs=3, fargnames=_rnn_argnames,
          finfer_params=_rnn_param_shapes,
          fvisible=lambda p, n: n if p.get("state_outputs") else 1)
def _rnn(*inputs, state_size=0, num_layers=1, bidirectional=False, mode="lstm",
         p=0.0, state_outputs=False, lstm_state_clip_min=None,
         lstm_state_clip_max=None, rng=None, is_train=False):
    """ref: src/operator/rnn.cc (fused RNN); returns (out, hy, cy)."""
    dirs = 2 if bidirectional else 1
    is_lstm = mode == "lstm"
    data = inputs[0]
    hx = inputs[1]
    idx = 2
    if is_lstm:
        cx = inputs[idx]
        idx += 1
    else:
        cx = jnp.zeros_like(hx)
    n_mats = num_layers * dirs
    w_ih = inputs[idx:idx + 2 * n_mats:2]
    w_hh = inputs[idx + 1:idx + 2 * n_mats:2]
    idx += 2 * n_mats
    b_ih = inputs[idx:idx + 2 * n_mats:2]
    b_hh = inputs[idx + 1:idx + 2 * n_mats:2]

    x = data
    hy, cy = [], []
    k = rng
    for layer in range(num_layers):
        outs = []
        for d in range(dirs):
            li = layer * dirs + d
            ys, hT, cT = _layer_scan(x, hx[li], cx[li], w_ih[li], w_hh[li],
                                     b_ih[li], b_hh[li], mode, reverse=d == 1)
            if is_lstm and lstm_state_clip_min is not None:
                cT = jnp.clip(cT, lstm_state_clip_min, lstm_state_clip_max)
            outs.append(ys)
            hy.append(hT)
            cy.append(cT)
        x = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if is_train and p > 0.0 and layer < num_layers - 1:
            k, sub = jax.random.split(k)
            x = inverted_dropout(x, p, x.shape, sub, "RNN")
    return x, jnp.stack(hy), jnp.stack(cy)


# ---------------------------------------------------------------------------
# CTC loss (ref: src/operator/contrib/ctc_loss.cc — embedded warp-ctc;
# here: log-space alpha recursion as one lax.scan over time, batched by vmap)
# ---------------------------------------------------------------------------

_NEG_INF = -1e30


def _ctc_single(log_probs, ext, ext_len, data_len):
    """Negative log-likelihood for one sample.

    log_probs: (T, C) log-softmax scores; ext: (S,) extended label sequence
    (blank interleaved, padded); ext_len: true extended length; data_len:
    true input length."""
    T, C = log_probs.shape
    S = ext.shape[0]
    s_idx = jnp.arange(S)
    valid = s_idx < ext_len

    # alpha_0
    a0 = jnp.full((S,), _NEG_INF)
    a0 = a0.at[0].set(log_probs[0, ext[0]])
    a0 = a0.at[1].set(jnp.where(ext_len > 1, log_probs[0, ext[1]], _NEG_INF))

    same_as_2back = jnp.concatenate(
        [jnp.array([True, True]), ext[2:] == ext[:-2]])

    def step(alpha, lp):
        shift1 = jnp.concatenate([jnp.array([_NEG_INF]), alpha[:-1]])
        shift2 = jnp.concatenate([jnp.full((2,), _NEG_INF), alpha[:-2]])
        shift2 = jnp.where(same_as_2back, _NEG_INF, shift2)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, shift1), shift2)
        new_alpha = merged + lp[ext]
        new_alpha = jnp.where(valid, new_alpha, _NEG_INF)
        return new_alpha, new_alpha

    _, alphas = lax.scan(step, a0, log_probs[1:])
    alphas = jnp.concatenate([a0[None], alphas])          # (T, S)
    a_last = alphas[jnp.maximum(data_len - 1, 0)]
    ll = jnp.logaddexp(a_last[jnp.maximum(ext_len - 1, 0)],
                       a_last[jnp.maximum(ext_len - 2, 0)])
    return -ll


@register("CTCLoss", num_inputs=None,
          aliases=("ctc_loss", "_contrib_CTCLoss", "_contrib_ctc_loss"))
def _ctc_loss(data, label, data_lengths=None, label_lengths=None,
              use_data_lengths=False, use_label_lengths=False,
              blank_label="first"):
    """ref: src/operator/contrib/ctc_loss.cc. data (T,N,C) activations
    (softmax applied internally, as the reference does); label (N,L),
    padded with 0 ('first') / -1 ('last')."""
    T, N, C = data.shape
    log_probs = jax.nn.log_softmax(data, axis=-1)
    label = label.astype(jnp.int32)
    if blank_label == "first":
        blank = 0
        lab_valid = label > 0
        lab = label
    else:
        blank = C - 1
        lab_valid = label >= 0
        lab = jnp.where(lab_valid, label, 0)
    if use_label_lengths and label_lengths is not None:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        lab_len = lab_valid.sum(axis=1).astype(jnp.int32)
    if use_data_lengths and data_lengths is not None:
        dat_len = data_lengths.astype(jnp.int32)
    else:
        dat_len = jnp.full((N,), T, jnp.int32)

    L = label.shape[1]
    S = 2 * L + 1
    # extended sequence: blank, l1, blank, l2, ..., blank
    ext = jnp.full((N, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    ext_len = 2 * lab_len + 1

    return jax.vmap(_ctc_single, in_axes=(1, 0, 0, 0))(
        log_probs, ext, ext_len, dat_len)
