"""Plain float32 reference of the first steps of FrODO training, for any
model that a configuration brings (``bench/models/<model_code>.py``).

It imports nothing of the program.  Straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``: no scan, no kernels, no
checkpoint policies.  So that it fits one chip at the timed sizes, each
agent's gradient is taken layer by layer by the model's ``agent_grad``
(the forward pass keeps each layer's input; the backward pass takes each
layer's ``vjp`` from it), the update and the consensus mix go leaf by
leaf, and the past gradients of the memory wait in host memory.

One step, for every agent i (Algorithm 1 of FrODO, with the exponential-sum
memory the configuration states):

    g_i  = clip(grad f_i(x_i))          (one global norm over all agents)
    M_i  = sum_{n>=1} h_n g_i^(t-n),    h_n = sum_k c_k r_k^n
    x_i <- x_i - alpha g_i - beta M_i
    x   <- W x                          (complete graph: the mean)

The memory is kept as the past gradients themselves, which is exact for the
few steps that are compared.

``quant="int8"`` is the control: every matmul operand of the forward pass
rounded to int8 with one scale per tensor (straight-through in the
backward pass), which a model's matmuls get through ``mm``.  ``fault``
plants one of the faults the comparison must catch: ``"unchanged"`` (the
state is returned as it came), ``"half_batch"`` (the loss is the mean over
half of the tokens), ``"no_mix"`` (no exchange between agents).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


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


# ------------------------------------------------ quantised matmuls

def _q(x, quant):
    if quant is None:
        return x
    if quant != "int8":
        raise ValueError(quant)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    xq = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(xq - x)


def mm(spec, a, b, quant):
    """``einsum`` at ``highest`` precision, with the operands rounded as
    ``quant`` says (None: not at all)."""
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HIGHEST)


def split_layers(flat: dict, stacks: dict) -> dict:
    """``<stack>/<leaf>`` of shape (L, ...) -> ``<stack>.<i>/<leaf>``, for
    each top-level ``{stack: L}`` of the model's ``stacks``."""
    out = {}
    for k, v in flat.items():
        stack = k.split("/", 1)[0]
        if stack in stacks:
            for i in range(stacks[stack]):
                out[f"{stack}.{i}/{k[len(stack) + 1:]}"] = v[i]
        else:
            out[k] = v
    return out


def join_layers(norms: dict, stacks: dict) -> dict:
    """Per-layer norms -> the norm of each stacked leaf."""
    out = {}
    for k, v in norms.items():
        head, rest = k.split("/", 1)
        stack, _, i = head.rpartition(".")
        if stack in stacks and i.isdigit():
            k = f"{stack}/{rest}"
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


def run(model, params: list, batches: list, config: dict, devices: list,
        quant=None, fault=None) -> dict:
    """Runs ``len(batches)`` steps of ``model`` (the configuration's model
    module) from ``params`` (``agent_slices``: one flat f32 dict per agent,
    agent a on ``devices[a % len(devices)]``) and returns, per agent, the
    step losses, the per-leaf norms of the first (clipped) gradient and the
    per-leaf norms of the parameters' change, leaves as the program stacks
    them."""
    m, tr = config["model"], config["trainer"]
    stacks = model.stacks(m)
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
            out = [model.agent_grad(x[a],
                                    jax.device_put(batch["tokens"][a],
                                                   dev[a]),
                                    jax.device_put(batch["labels"][a],
                                                   dev[a]),
                                    m, quant, half) for a in range(A)]
            losses.append([float(l) for l, _ in out])
            grads = [g for _, g in out]
            del out
            gn = float(np.sqrt(sum(float(_sq(g)) for g in grads)))
            scale = min(1.0, clip / max(gn, 1e-9))
            if t == 0:
                first = [join_layers({k: float(v) * scale
                                      for k, v in _norms(g).items()}, stacks)
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
             for k, v in x[a].items()}, stacks) for a in range(A)]
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


def agent_slices(stacked_flat: dict, n_agents: int, stacks: dict,
                 devices: list) -> list:
    """Per-agent f32 copies of the benchmark's stacked weights, each layer's
    leaves apart."""
    out = []
    for a in range(n_agents):
        d = devices[a % len(devices)]
        one = {k: v[a] for k, v in stacked_flat.items()}
        out.append({k: jax.device_put(v, d).astype(jnp.float32)
                    for k, v in split_layers(one, stacks).items()})
    return out

