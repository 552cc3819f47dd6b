"""The benchmark's plain reference and the comparison that decides
``correct``.

``init_params`` makes a configuration's weights from a seed, on the device,
in one jitted call.  ``forward`` is a straightforward ``lax`` interpreter
over the configuration file's layer table, in NCHW, with every matmul and
convolution at the precision asked for:

* ``highest``: float32 at full precision (what the configuration states);
* ``high``: three bf16 passes (hi*hi + hi*lo + lo*hi), the precision just
  below it, written out so that it means the same on every backend;
* ``bf16``: one bf16 pass.

Nothing here imports the program under test; the program is handed the
weights made here.  ``check_numbers`` is the comparison: per row, the
largest probability difference relative to the row's largest probability
(``prob_gap``), and the root mean square difference of ``log p`` centred
per row, relative to the reference row's largest centred logit
(``logit_rms``); each over the worst row.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.spec import in_dims, producers

PROB_FLOOR = 1e-30
FC_BIAS_STD = 0.05


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    state = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(state, np.uint32))


def param_shapes(cfg: Dict) -> Dict:
    out = {}
    for l, ci in zip(cfg["layers"], in_dims(cfg)):
        if l["kind"] == "conv":
            out[l["name"]] = {"w": (l["out"], ci, l["kernel"], l["kernel"])}
        elif l["kind"] == "fc":
            out[l["name"]] = {"w": (ci, l["out"]), "b": (l["out"],)}
    return out


def init_params(cfg: Dict, seed: int, sharding=None) -> Dict:
    """Weights with variance 1/fan-in (conv weights ``[Co, Ci, F, F]``, fc
    weights ``[features, out]`` and biases), made in one jitted call."""
    shapes = param_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, leaves) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            w = leaves["w"]
            std = 1.0 / math.sqrt(math.prod(w[1:]) if len(w) == 4 else w[0])
            out[name] = {"w": jax.random.normal(k, w, jnp.float32) * std}
            if "b" in leaves:
                out[name]["b"] = FC_BIAS_STD * jax.random.normal(
                    jax.random.fold_in(k, 1), leaves["b"], jnp.float32)
        return out

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))


def _split(a):
    """``a`` as ``hi + lo`` in bf16, each rounded to nearest even.  ``hi``
    is rounded in integer arithmetic on the float32 bits, which no compiler
    may fold away as XLA folds a round trip through bf16 on a TPU."""
    bits = lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    hi = lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _mm(op, a, b, precision: str):
    """``op(a, b)`` (a dot or a convolution) at ``precision``."""
    if precision == "highest":
        return op(a, b, lax.Precision.HIGHEST)
    ah, al = _split(a)
    bh, bl = _split(b)
    if precision == "bf16":
        return op(ah, bh, lax.Precision.DEFAULT)
    if precision == "high":
        return (op(al, bh, lax.Precision.DEFAULT)
                + op(ah, bl, lax.Precision.DEFAULT)
                + op(ah, bh, lax.Precision.DEFAULT))
    raise ValueError(f"unknown precision {precision!r}")


def forward(params: Dict, x, cfg: Dict, precision: str = "highest"):
    """Probabilities ``[N, classes]`` of NCHW float32 images ``x``."""
    outs = {-1: x}
    for i, (l, ins) in enumerate(zip(cfg["layers"], producers(cfg))):
        h = outs[ins[0]]
        k = l["kind"]
        if k == "conv":
            s, p = l["stride"], l["pad"]

            def conv(a, b, prec, s=s, p=p):
                return lax.conv_general_dilated(
                    a, b, (s, s), [(p, p), (p, p)],
                    dimension_numbers=("NCHW", "OIHW", "NCHW"),
                    precision=prec, preferred_element_type=jnp.float32)
            h = _mm(conv, h, params[l["name"]]["w"], precision)
        elif k == "relu":
            h = jnp.maximum(h, 0.0)
        elif k == "pool":
            win = (1, 1, l["kernel"], l["kernel"])
            st = (1, 1, l["stride"], l["stride"])
            if l["op"] == "max":
                h = lax.reduce_window(h, -jnp.inf, lax.max, win, st, "VALID")
            else:
                h = lax.reduce_window(h, 0.0, lax.add, win, st,
                                      "VALID") / l["kernel"] ** 2
        elif k == "flatten":
            h = h.reshape(h.shape[0], -1)
        elif k == "fc":
            def dot(a, b, prec):
                return jnp.dot(a, b, precision=prec,
                               preferred_element_type=jnp.float32)
            h = (_mm(dot, h, params[l["name"]]["w"], precision)
                 + params[l["name"]]["b"])
        elif k == "add":
            h = h + outs[ins[1]]
        elif k == "softmax":
            h = jax.nn.softmax(h, axis=-1)
        else:
            raise ValueError(f"{l['name']}: unknown layer kind {k!r}")
        outs[i] = h
    return outs[len(cfg["layers"]) - 1]


def reference_probs(params: Dict, images: np.ndarray, cfg: Dict,
                    precision: str = "highest", block: int = 32,
                    device=None) -> np.ndarray:
    """``forward`` over ``images`` in blocks of ``block`` rows (one compiled
    program), on ``device``; the last block is padded and trimmed."""
    f = jax.jit(lambda p, x: forward(p, x, cfg, precision))
    if device is not None:
        params = jax.device_put(params, device)
    n = len(images)
    out = []
    for i in range(0, n, block):
        xb = images[i:i + block]
        if len(xb) < block:
            xb = np.concatenate(
                [xb, np.zeros((block - len(xb),) + xb.shape[1:], xb.dtype)])
        xb = jax.device_put(xb, device)
        out.append(np.asarray(f(params, xb))[:min(block, n - i)])
    return np.concatenate(out).astype(np.float32)


def prob_gap(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: largest probability difference over the row's largest
    reference probability."""
    scale = np.abs(ref).max(axis=1)
    return np.abs(got - ref).max(axis=1) / scale


def centred_logits(p: np.ndarray) -> np.ndarray:
    z = np.log(np.maximum(p.astype(np.float64), PROB_FLOOR))
    return z - z.mean(axis=1, keepdims=True)


def logit_rms(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: root mean square difference of centred logits over the
    reference row's largest centred logit."""
    zg, zr = centred_logits(got), centred_logits(ref)
    return (np.sqrt(np.mean((zg - zr) ** 2, axis=1))
            / np.abs(zr).max(axis=1))


def check_numbers(got: np.ndarray, ref: np.ndarray) -> dict:
    """The numbers ``correct`` compares, each the worst row's: the
    probability gap and the root mean square logit gap.  A row that is
    not finite reads infinite."""
    finite = np.isfinite(got).all(axis=1)
    with np.errstate(invalid="ignore"):
        pg, lr = prob_gap(got, ref), logit_rms(got, ref)
    return {"prob_gap": float(np.max(np.where(finite, pg, np.inf))),
            "logit_rms": float(np.max(np.where(finite, lr, np.inf)))}

