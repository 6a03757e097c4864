"""Plain reference of the Qwen2 architecture (Qwen1.5 checkpoints), for training.

Written from the published description (Hugging Face ``Qwen2ForCausalLM``) in
straightforward ``jax.numpy``; it imports nothing of the program under test.
One decoder layer, for hidden states ``x``::

    h = x + o_proj(attn(rope(q_proj(rms(x))), rope(k_proj(rms(x))), v_proj(rms(x))))
    y = h + down_proj(silu(gate_proj(rms(h))) * up_proj(rms(h)))

with bias on q, k and v only, causal softmax attention scaled by
``head_dim ** -0.5``, RMSNorm ``w * x / sqrt(mean(x**2) + eps)``, a final
RMSNorm and an output head (the token embedding, transposed, when tied).

Departures, each forced by the layout the weights are stored in:

* RoPE rotates the pairs ``(2i, 2i+1)`` of a head, where the published code
  rotates ``(i, i + head_dim/2)``. The two are the same function under a fixed
  permutation of the columns of ``q_proj`` and ``k_proj`` (and their biases),
  so with weights drawn at random they are the same model.
* The vocabulary is padded to a multiple of ``vocab_pad_multiple`` rows; the
  padded rows are ordinary random rows, and the logits, the cross-entropy and
  the distillation term run over the padded width.

Precision: ``"highest"`` computes every matrix product in float32 at
``Precision.HIGHEST``; ``"fp8"`` rounds both operands of every product, and the
cotangent in its backward, to float8 e4m3 with a per-tensor scale (the control:
one precision below the bfloat16 products the configuration states);
``"bf16"`` rounds the operands to bfloat16 (a witness of the program's own
precision).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.shapes import dims

PRECISIONS = ("highest", "bf16", "fp8")
LOSS_ROWS = 256          # logits are formed this many tokens at a time


# ----------------------------------------------------------------------------
# sizes and the layout of the weights
# ----------------------------------------------------------------------------

def param_layout(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, int]]:
    """``path -> (shape, kind, fan_in)`` of one model's weights.

    Per-layer weights carry a leading axis of ``num_hidden_layers``. ``kind``
    is ``matrix``, ``embed``, ``norm`` or ``bias``; ``fan_in`` scales a matrix.
    """
    z = dims(cfg)
    d, h, kv, hd, ff, L, V = (z[k] for k in ("d", "h", "kv", "hd", "ff", "L", "V"))
    lay = {
        "embed/tokens": ((V, d), "embed", d),
        "final_norm/scale": ((d,), "norm", d),
        "layers/sub0/norm1/scale": ((L, d), "norm", d),
        "layers/sub0/norm2/scale": ((L, d), "norm", d),
        "layers/sub0/mix/wq": ((L, d, h, hd), "matrix", d),
        "layers/sub0/mix/wk": ((L, d, kv, hd), "matrix", d),
        "layers/sub0/mix/wv": ((L, d, kv, hd), "matrix", d),
        "layers/sub0/mix/wo": ((L, h * hd, d), "matrix", h * hd),
        "layers/sub0/ffn/w_gate": ((L, d, ff), "matrix", d),
        "layers/sub0/ffn/w_up": ((L, d, ff), "matrix", d),
        "layers/sub0/ffn/w_down": ((L, ff, d), "matrix", ff),
    }
    if cfg["qkv_bias"]:
        lay["layers/sub0/mix/bq"] = ((L, h, hd), "bias", d)
        lay["layers/sub0/mix/bk"] = ((L, kv, hd), "bias", d)
        lay["layers/sub0/mix/bv"] = ((L, kv, hd), "bias", d)
    if not cfg["tie_word_embeddings"]:
        lay["embed/head"] = ((d, V), "matrix", d)
    return lay


# ----------------------------------------------------------------------------
# matrix products at a stated precision
# ----------------------------------------------------------------------------

def _fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _ein(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein_fp8(spec, a, b):
    return _ein(spec, _fp8(a), _fp8(b))


def _ein_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _ein(spec, qa, qb), (qa, qb)


def _ein_fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(functools.partial(_ein, spec), *res)
    return vjp(_fp8(g))


_ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


def _bf16(spec, a, b):
    return _ein(spec, a.astype(jnp.bfloat16).astype(jnp.float32),
                b.astype(jnp.bfloat16).astype(jnp.float32))


def matmul(precision: str):
    if precision == "highest":
        return _ein
    if precision == "fp8":
        return _ein_fp8
    if precision == "bf16":
        return _bf16
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


# ----------------------------------------------------------------------------
# the forward pass
# ----------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, H, hd); rotates the pairs (2i, 2i+1) by position * freq_i."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _layer(cfg, mm, x, p):
    z = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s, _ = x.shape
    a = _rms(x, p["norm1/scale"], eps)
    q = mm("bsd,dhk->bshk", a, p["mix/wq"])
    k = mm("bsd,dhk->bshk", a, p["mix/wk"])
    v = mm("bsd,dhk->bshk", a, p["mix/wv"])
    if "mix/bq" in p:
        q, k, v = q + p["mix/bq"], k + p["mix/bk"], v + p["mix/bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    g = z["h"] // z["kv"]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    scores = mm("bshk,bthk->bhst", q, k) * (z["hd"] ** -0.5)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o = mm("bhst,bthk->bshk", w, v).reshape(b, s, z["h"] * z["hd"])
    x = x + mm("bse,ed->bsd", o, p["mix/wo"])
    a = _rms(x, p["norm2/scale"], eps)
    up = jax.nn.silu(mm("bsd,df->bsf", a, p["ffn/w_gate"])) \
        * mm("bsd,df->bsf", a, p["ffn/w_up"])
    return x + mm("bsf,fd->bsd", up, p["ffn/w_down"])


def hidden(cfg: Dict, params: Dict, tokens: jax.Array,
           precision: str = "highest") -> jax.Array:
    """Final-normed hidden states (B, S, d) of one model, in float32."""
    mm = matmul(precision)
    x = params["embed/tokens"][tokens]
    layers = {k[len("layers/sub0/"):]: v for k, v in params.items()
              if k.startswith("layers/")}

    @jax.checkpoint
    def body(x, p):
        return _layer(cfg, mm, x, p), None

    x, _ = jax.lax.scan(body, x, layers)
    return _rms(x, params["final_norm/scale"], cfg["rms_norm_eps"])


def head_weight(params: Dict) -> jax.Array:
    return params["embed/head"] if "embed/head" in params \
        else params["embed/tokens"].T


# ----------------------------------------------------------------------------
# losses and the training step
# ----------------------------------------------------------------------------

def _row_blocks(x, rows):
    t = x.shape[0]
    return x.reshape(t // rows, rows, *x.shape[1:])


def model_losses(cfg: Dict, peers: list, tokens, labels, mask,
                 precision: str = "highest"):
    """Per model ``(cross-entropy, mean distillation MSE)`` on one batch.

    ``peers`` holds each model's weights; every model reads the same batch
    (coordinated sampling). Model i's distillation term is the mean over the
    other models j of ``mean_tokens mean_vocab (z_i - stop_gradient(z_j))**2``
    on logits ``z``. The logits are formed ``LOSS_ROWS`` tokens at a time.
    """
    mm = matmul(precision)
    n = len(peers)
    hs = [hidden(cfg, p, tokens, precision).reshape(-1, cfg["hidden_size"])
          for p in peers]
    ws = [head_weight(p) for p in peers]
    lab = labels.reshape(-1)
    m = mask.reshape(-1).astype(jnp.float32)
    rows = min(LOSS_ROWS, lab.shape[0])
    blocks = ([_row_blocks(h, rows) for h in hs], _row_blocks(lab, rows),
              _row_blocks(m, rows))

    @jax.checkpoint
    def block(hb, lb, mb):
        z = [mm("td,dv->tv", hb[i], ws[i]) for i in range(n)]
        zt = [jax.lax.stop_gradient(x) for x in z]
        out = []
        for i in range(n):
            lse = jax.scipy.special.logsumexp(z[i], axis=-1)
            true = jnp.take_along_axis(z[i], lb[:, None], axis=-1)[:, 0]
            ce = jnp.sum((lse - true) * mb)
            dist = 0.0
            for j in range(n):
                if j != i:
                    dist = dist + jnp.sum(
                        jnp.mean((z[i] - zt[j]) ** 2, axis=-1) * mb)
            out.append(jnp.stack([ce, dist / max(1, n - 1)]))
        return jnp.stack(out)                       # (n, 2)

    def scan_body(acc, xs):
        return acc + block(*xs), None

    sums, _ = jax.lax.scan(scan_body, jnp.zeros((n, 2), jnp.float32), blocks)
    return sums / jnp.maximum(jnp.sum(m), 1.0)


def total_loss(cfg: Dict, traffic: Dict, peers: list, batch: Dict,
               precision: str = "highest") -> jax.Array:
    """The loss one step minimizes: the mean over models of
    ``ce_i + alpha * distill_i`` (a single model: its cross-entropy)."""
    parts = model_losses(cfg, peers, batch["tokens"], batch["labels"],
                         batch["mask"], precision)
    alpha = traffic["alpha"] if len(peers) > 1 else 0.0
    return jnp.mean(parts[:, 0] + alpha * parts[:, 1])


def sgd_momentum(params, grads, mom, lr, momentum, weight_decay):
    mom = jax.tree.map(lambda m, g, p: momentum * m + g + weight_decay * p,
                       mom, grads, params)
    params = jax.tree.map(lambda p, m: p - lr * m, params, mom)
    return params, mom
