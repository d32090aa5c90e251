"""Plain float32 reference of the first steps of FrODO training for the dense
sliding-window transformer (h2o-danube-1.8b, arXiv:2401.16818).

It imports nothing of the program.  Straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``: no scan, no kernels, no
checkpoint policies.  So that it fits one chip at the timed sizes, each
agent's gradient is taken layer by layer (the forward pass keeps each
layer's input; the backward pass takes each layer's ``vjp`` from it), the
update and the consensus mix go leaf by leaf, and the past gradients of the
memory wait in host memory.

One step, for every agent i (Algorithm 1 of FrODO, with the exponential-sum
memory the configuration states):

    g_i  = clip(grad f_i(x_i))          (one global norm over all agents)
    M_i  = sum_{n>=1} h_n g_i^(t-n),    h_n = sum_k c_k r_k^n
    x_i <- x_i - alpha g_i - beta M_i
    x   <- W x                          (complete graph: the mean)

The memory is kept as the past gradients themselves, which is exact for the
few steps that are compared.  The model follows the published description
with the program's conventions where the paper leaves a choice: RoPE
rotates interleaved pairs, GQA query head h reads key/value head
h // (H / G), RMSNorm scales after normalising.

``quant="int8"`` is the control: every matmul operand of the forward pass
rounded to int8 with one scale per tensor (straight-through in the
backward pass).  ``fault`` plants one of the faults the comparison must
catch: ``"unchanged"`` (the state is returned as it came), ``"half_batch"``
(the loss is the mean over half of the tokens), ``"no_mix"`` (no exchange
between agents).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


# ------------------------------------------------------------ the memory

def expsum_fit(T: int, lam: float, K: int):
    """Rates r_k and coefficients c_k with sum_k c_k r_k^n ~= n^(lam-1) on
    n = 1..T: decay times log-spaced in [0.5, T], coefficients by weighted
    least squares (relative error controlled across the tail)."""
    n = np.arange(1, T + 1, dtype=np.float64)
    mu = n ** (lam - 1.0)
    mu = mu / mu.max()
    rates = np.exp(-1.0 / np.geomspace(0.5, 1.0 * T, K))
    A = rates[None, :] ** n[:, None]
    w = 1.0 / np.maximum(mu, 1e-12)
    coeffs, *_ = np.linalg.lstsq(A * w[:, None], mu * w, rcond=None)
    return rates, coeffs


def memory_weights(tr: dict, n_max: int) -> np.ndarray:
    """h_n for n = 1..n_max."""
    rates, coeffs = expsum_fit(tr["T"], tr["lam"], tr["K"])
    n = np.arange(1, n_max + 1, dtype=np.float64)
    return (rates[None, :] ** n[:, None]) @ coeffs


def mixing_matrix(tr: dict) -> np.ndarray:
    if tr["topology"] != "complete":
        raise ValueError(f"the reference mixes complete graphs only, not "
                         f"{tr['topology']!r}")
    A = tr["agents"]
    return np.full((A, A), 1.0 / A)


# ------------------------------------------------------------- the model

def _q(x, quant):
    if quant is None:
        return x
    if quant != "int8":
        raise ValueError(quant)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    xq = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(xq - x)


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HIGHEST)


def rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (B, S, heads, hd); rotates interleaved pairs (0,1), (2,3), ..."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def block(bp, x, m, quant=None):
    """One decoder layer: pre-norm GQA attention (causal, sliding window)
    and a gated SiLU MLP, each added to the residual stream."""
    H, G = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    S = x.shape[1]
    h = rmsnorm(bp["ln1/scale"], x, m["norm_eps"])
    q = rope(_mm("bsd,dhk->bshk", h, bp["attn/wq/w"], quant), m["rope_theta"])
    k = rope(_mm("bsd,dgk->bsgk", h, bp["attn/wk/w"], quant), m["rope_theta"])
    v = _mm("bsd,dgk->bsgk", h, bp["attn/wv/w"], quant)
    k = jnp.repeat(k, H // G, axis=2)
    v = jnp.repeat(v, H // G, axis=2)
    s = _mm("bshk,bthk->bhst", q, k, quant) / np.sqrt(hd)
    dist = np.arange(S)[:, None] - np.arange(S)[None, :]
    ok = dist >= 0
    if m["window"] > 0:
        ok &= dist < m["window"]
    s = jnp.where(jnp.asarray(ok), s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhst,bthk->bshk", p, v, quant)
    x = x + _mm("bshk,hkd->bsd", o, bp["attn/wo/w"], quant)
    h = rmsnorm(bp["ln2/scale"], x, m["norm_eps"])
    up = _mm("bsd,df->bsf", h, bp["mlp/up/w"], quant)
    if m["gated_mlp"]:
        up = jax.nn.silu(_mm("bsd,df->bsf", h, bp["mlp/gate/w"], quant)) * up
    else:
        up = jax.nn.silu(up)
    return x + _mm("bsf,fd->bsd", up, bp["mlp/down/w"], quant)


def head_loss(ln_f, w, x, labels, m, quant=None, half=False):
    """Token-mean cross entropy of the final norm and the LM head."""
    h = rmsnorm(ln_f, x, m["norm_eps"])
    logits = _mm("bsd,dv->bsv", h, w, quant)
    ce = (jax.nn.logsumexp(logits, -1)
          - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
    if half:
        ce = ce[:, : ce.shape[1] // 2]
    return jnp.mean(ce)


@partial(jax.jit, static_argnames=("m", "quant"))
def _block_fwd(bp, x, m, quant):
    return block(bp, x, dict(m), quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _block_bwd(bp, x, dy, m, quant):
    _, vjp = jax.vjp(lambda p, z: block(p, z, dict(m), quant), bp, x)
    return vjp(dy)


@partial(jax.jit, static_argnames=("m", "quant", "half"))
def _head_grad(ln_f, w, x, labels, m, quant, half):
    return jax.value_and_grad(
        lambda a, b, z: head_loss(a, b, z, labels, dict(m), quant, half),
        argnums=(0, 1, 2))(ln_f, w, x)


@jax.jit
def _embed(table, tokens):
    return table[tokens]


@jax.jit
def _embed_grad(table, tokens, dx):
    return jnp.zeros_like(table).at[tokens.reshape(-1)].add(
        dx.reshape(-1, dx.shape[-1]))


def agent_grad(p: dict, tokens, labels, m: dict, quant=None, half=False):
    """(loss, grads) of one agent, layer by layer; ``p`` is flat f32 with
    each layer's leaves apart (``split_layers``)."""
    mk = tuple(sorted(m.items()))
    L = m["n_layers"]
    layer = [{k[len(f"blocks.{i}/"):]: v for k, v in p.items()
              if k.startswith(f"blocks.{i}/")} for i in range(L)]
    xs = [_embed(p["embed/table"], tokens)]
    for bp in layer:
        xs.append(_block_fwd(bp, xs[-1], mk, quant))
    w = p["embed/table"].T if m["tie_embeddings"] else p["lm_head/w"]
    loss, (d_lnf, d_w, dx) = _head_grad(p["ln_f/scale"], w, xs[-1], labels,
                                        mk, quant, half)
    grads = {"ln_f/scale": d_lnf}
    for i in reversed(range(L)):
        d, dx = _block_bwd(layer[i], xs[i], dx, mk, quant)
        grads.update({f"blocks.{i}/{k}": v for k, v in d.items()})
        xs[i + 1] = None
    d_table = _embed_grad(p["embed/table"], tokens, dx)
    if m["tie_embeddings"]:
        d_table = d_table + d_w.T
    else:
        grads["lm_head/w"] = d_w
    grads["embed/table"] = d_table
    return loss, grads


def split_layers(flat: dict, n_layers: int) -> dict:
    """``blocks/<leaf>`` of shape (L, ...) -> ``blocks.<i>/<leaf>``."""
    out = {}
    for k, v in flat.items():
        if k.startswith("blocks/"):
            for i in range(n_layers):
                out[f"blocks.{i}/{k[len('blocks/'):]}"] = v[i]
        else:
            out[k] = v
    return out


def join_layers(norms: dict) -> dict:
    """Per-layer norms -> the norm of each stacked leaf."""
    out = {}
    for k, v in norms.items():
        if k.startswith("blocks."):
            k = "blocks/" + k.split("/", 1)[1]
        out[k] = out.get(k, 0.0) + v * v
    return {k: float(np.sqrt(v)) for k, v in out.items()}


@jax.jit
def _sq(g):
    return sum(jnp.sum(v * v) for v in g.values())


@jax.jit
def _norms(g):
    return {k: jnp.sqrt(jnp.sum(v * v)) for k, v in g.items()}


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum((a - b) ** 2))


@partial(jax.jit, donate_argnums=0)
def _update_leaf(p, g, past, scale, alpha, beta, h):
    """x - alpha g - beta M with M = sum_n h_n g^(t-n), g already clipped
    by ``scale``; ``past`` holds the clipped g^(t-1), g^(t-2), ..."""
    mem = sum(hn * gp for hn, gp in zip(h, past)) if past else 0.0
    return p - alpha * scale * g - beta * mem


def update(p: dict, g: dict, past: list, scale, alpha, beta, h) -> dict:
    """One agent's step, leaf by leaf; the past gradients wait on the host,
    so that the device holds no more than the parameters and this step's
    gradients of every agent."""
    dev = next(iter(p.values())).devices().pop()
    return {k: _update_leaf(p[k], g[k],
                            tuple(jax.device_put(gp[k], dev) for gp in past),
                            scale, alpha, beta, tuple(h))
            for k in p}


@jax.jit
def _scale(g, s):
    return {k: v * s for k, v in g.items()}


def run(params: list, batches: list, config: dict, devices: list,
        quant=None, fault=None) -> dict:
    """Runs ``len(batches)`` steps from ``params`` (``agent_slices``: one
    flat f32 dict per agent, agent a on ``devices[a % len(devices)]``) and
    returns, per agent, the step losses, the per-leaf norms of the first
    (clipped) gradient and the per-leaf norms of the parameters' change,
    leaves as the program stacks them."""
    m, tr = config["model"], config["trainer"]
    A = len(params)
    dev = [devices[a % len(devices)] for a in range(A)]
    W = mixing_matrix(tr)
    h = [float(x) for x in memory_weights(tr, len(batches))]
    clip = config["grad_clip"] * np.sqrt(A)
    half = fault == "half_batch"
    x0 = [{k: np.asarray(v) for k, v in p.items()} for p in params]
    x = [dict(p) for p in params]
    del params
    past = [[] for _ in range(A)]
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches):
            out = [agent_grad(x[a],
                              jax.device_put(batch["tokens"][a], dev[a]),
                              jax.device_put(batch["labels"][a], dev[a]),
                              m, quant, half) for a in range(A)]
            losses.append([float(l) for l, _ in out])
            grads = [g for _, g in out]
            del out
            gn = float(np.sqrt(sum(float(_sq(g)) for g in grads)))
            scale = min(1.0, clip / max(gn, 1e-9))
            if t == 0:
                first = [join_layers({k: float(v) * scale
                                      for k, v in _norms(g).items()})
                         for g in grads]
            if fault == "unchanged":
                continue
            last = t == len(batches) - 1
            for a in range(A):
                x[a] = update(x[a], grads[a], past[a][::-1], scale,
                              tr["alpha"], tr["beta"], h[:len(past[a])])
                if not last:
                    past[a].append({k: np.asarray(v) for k, v in
                                    _scale(grads[a], scale).items()})
            del grads
            if fault != "no_mix":
                x = mix(x, W, dev)
        del past
        change = [join_layers(
            {k: float(_diff_norm(v, jax.device_put(x0[a][k], dev[a])))
             for k, v in x[a].items()}) for a in range(A)]
    return {"losses": losses, "first_grad": first, "change": change}


def mix(x: list, W: np.ndarray, dev: list) -> list:
    """x_a <- sum_b W[a, b] x_b, leaf by leaf."""
    A = len(x)
    out = [dict() for _ in range(A)]
    for k in x[0]:
        for a in range(A):
            acc = None
            for b in range(A):
                if W[a, b] == 0:
                    continue
                term = W[a, b] * jax.device_put(x[b][k], dev[a])
                acc = term if acc is None else acc + term
            out[a][k] = acc
        for a in range(A):
            x[a][k] = None
    return out


def agent_slices(stacked_flat: dict, n_agents: int, n_layers: int,
                 devices: list) -> list:
    """Per-agent f32 copies of the benchmark's stacked weights, each layer's
    leaves apart."""
    out = []
    for a in range(n_agents):
        d = devices[a % len(devices)]
        one = {k: v[a] for k, v in stacked_flat.items()}
        out.append({k: jax.device_put(v, d).astype(jnp.float32)
                    for k, v in split_layers(one, n_layers).items()})
    return out

