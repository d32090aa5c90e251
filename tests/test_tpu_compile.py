"""Compiles for a described TPU v5e (no chip attached): the fused FrODO
kernels at h2o-danube-1.8b leaf shapes, and the one-chip train step at the
cut that chip_smoke.py runs.  Nothing executes; Mosaic and XLA's TPU
compiler refuse here what the chip would refuse.  The compiled step's
operations carry the step's name scopes, which a device trace reports
beside each operation.

Every chip compile test lives in this file, and the topology is described
only inside the fixture below, so that one pytest worker loads the TPU
library."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.h2o_danube_1_8b import CHIP_TRAIN
from repro.kernels import ops
from repro.launch.train import build_trainer
from repro.training.train_step import abstract_train_state

HBM_BYTES = 15.75 * 2 ** 30     # what XLA may use of one v5e chip's 16 GiB
HEADROOM_BYTES = 2 * 2 ** 30

# agent-stacked leaves at the cut: an MLP matrix, the embedding, a norm
LEAVES = [(2, 2560, 6912), (2, 32000, 2560), (2, 2560)]

# a name scope inside an op_name path such as
# "jit(train_step)/vmap(transpose(jvp(train.fwd_bwd)))/dot_general"
SCOPE = re.compile(r"(?<=[/(])[A-Za-z_]\w*(?:\.\w+)+(?=[/)]|$)")
HEADER = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", LEAVES)
def test_exact_kernel_compiles(one_chip, shape):
    # T=40 is the trainer CLI's default; the embedding's history is cut to
    # T=16 so that the program fits the chip
    T = 16 if shape[1] == 32000 else 40
    compiled = ops.frodo_update.lower(
        _sds(shape, jnp.bfloat16, one_chip),
        _sds((T,) + shape, jnp.bfloat16, one_chip),
        _sds((), jnp.int32, one_chip), _sds((T,), jnp.float32, one_chip),
        alpha=0.02, beta=0.008).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("K,acc_dtype", [(4, jnp.bfloat16), (8, jnp.float32)])
@pytest.mark.parametrize("shape", LEAVES)
def test_expsum_kernel_compiles(one_chip, shape, K, acc_dtype):
    compiled = ops.frodo_expsum_update.lower(
        _sds(shape, jnp.bfloat16, one_chip),
        _sds((K,) + shape, acc_dtype, one_chip),
        _sds((K,), jnp.float32, one_chip), _sds((K,), jnp.float32, one_chip),
        alpha=0.02, beta=0.008).compile()
    assert "tpu_custom_call" in compiled.as_text()


_STEPS = {}


def _compiled_step(one_chip, use_kernel):
    """The step chip_smoke.py runs (metrics on, state donated), compiled
    once per test module; returns (compiled, abstract state)."""
    if use_kernel not in _STEPS:
        trainer = build_trainer(**CHIP_TRAIN, use_kernel=use_kernel,
                                collect_metrics=True)
        state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                             abstract_train_state(trainer.cfg, trainer.tc,
                                                  trainer.n_agents))
        A, B, S = (CHIP_TRAIN[k] for k in ("agents", "batch_per_agent",
                                           "seq"))
        batch = {k: _sds((A, B, S), jnp.int32, one_chip)
                 for k in ("tokens", "labels")}
        _STEPS[use_kernel] = (trainer.step_fn.lower(state, batch).compile(),
                              state)
    return _STEPS[use_kernel]


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "fused"])
def test_one_chip_train_step_fits(one_chip, use_kernel):
    """The step chip_smoke.py runs (metrics on, state donated) leaves at
    least HEADROOM_BYTES of the chip free."""
    compiled, state = _compiled_step(one_chip, use_kernel)
    mem = compiled.memory_analysis()
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes      # donated in place
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used <= HBM_BYTES - HEADROOM_BYTES, used / 2 ** 30
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel


def hlo_scopes(hlo: str) -> dict:
    """``{computation: [(instruction, outermost scope or None, called
    fusion computation or None)]}`` of a compiled module's text."""
    comps, comp = {}, None
    for line in hlo.splitlines():
        head = HEADER.match(line)
        if head:
            comp = comps.setdefault(head.group(1), [])
            continue
        inst = INSTRUCTION.match(line)
        if inst is None or comp is None:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        scope = SCOPE.search(op.group(1)) if op else None
        calls = re.search(r" fusion\(.*calls=%([^,\s]+)", line)
        comp.append((inst.group(1), scope and scope.group(0),
                     calls and calls.group(1)))
    return comps


def test_one_chip_step_operations_carry_the_step_scopes(one_chip):
    """The forward/backward, the FrODO update and the mix each name at
    least one operation that the device runs (a fusion or an unfused op).
    A fusion carries its root's op_name only, so a fusion whose fused
    instructions come from several scopes counts wholly to its root's:
    those are printed."""
    comps = hlo_scopes(_compiled_step(one_chip, False)[0].as_text())
    fused = {c for insts in comps.values() for _, _, c in insts if c}
    run = {scope for name, insts in comps.items() if name not in fused
           for _, scope, _ in insts}
    for scope in ("train.fwd_bwd", "frodo.update", "consensus.mix_uniform"):
        assert scope in run, (scope, sorted(s for s in run if s))
    for insts in comps.values():
        for name, scope, calls in insts:
            inner = {s for _, s, _ in comps.get(calls, ()) if s}
            if len(inner) > 1:
                print(f"{name}: counted to {scope}, fuses {sorted(inner)}")
