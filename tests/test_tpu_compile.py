"""Compiles for a described TPU v5e (no chip attached): the fused FrODO
kernels at h2o-danube-1.8b leaf shapes, the one-chip train step at the cut
that chip_smoke.py runs, and the four-agent step over a 2x2 mesh.  Nothing
executes; Mosaic and XLA's TPU compiler refuse here what the chip would
refuse.  The compiled step's operations carry the step's name scopes,
which a device trace reports beside each operation.  The steps are traced
with the described chip as JAX's default device, so that the exp-sum
update matches that chip's layouts, as it does on the chip itself.

Every chip compile test lives in this file, and the topology is described
only inside the fixture below, so that one pytest worker loads the TPU
library."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.configs.h2o_danube_1_8b import CHIP_TRAIN, FOUR_CHIP_TRAIN
from repro.core import memory as fmem
from repro.kernels import frodo_update as kfu
from repro.kernels import ops
from repro.launch.train import build_trainer
from repro.training.train_step import abstract_train_state

HBM_BYTES = 15.75 * 2 ** 30     # what XLA may use of one v5e chip's 16 GiB
HEADROOM_BYTES = 2 * 2 ** 30

# agent-stacked leaves at the cut: an MLP matrix, the embedding, a norm
LEAVES = [(2, 2560, 6912), (2, 32000, 2560), (2, 2560)]
# the benchmark cell's exp-sum leaves (2 agents, 4 layers): MLP in and out,
# a k/v projection flattened, the embedding, the head, and the attention
# projections as the model stores them
EXPSUM_LEAVES = [(2, 4, 2560, 6912), (2, 4, 6912, 2560), (2, 4, 2560, 640),
                 (2, 32000, 2560), (2, 2560, 32000), (2, 4, 2560, 32, 80),
                 (2, 4, 32, 80, 2560)]

# a name scope inside an op_name path such as
# "jit(train_step)/vmap(transpose(jvp(train.fwd_bwd)))/dot_general"
SCOPE = re.compile(r"(?<=[/(])[A-Za-z_]\w*(?:\.\w+)+(?=[/)]|$)")
HEADER = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")


@pytest.fixture(scope="module")
def topology():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


def _device(sharding):
    device, = sharding.device_set
    return device


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
@pytest.mark.parametrize("shape", EXPSUM_LEAVES)
def test_expsum_kernel_compiles(one_chip, shape, K, acc_dtype):
    """The one-pass exp-sum kernel at the cell's leaf shapes, each in the
    chip's default layout: its operands reach the kernel through bitcasts
    only (the layout-matching transposes), never a copy."""
    device = _device(one_chip)
    order = kfu.expsum_order(shape, jnp.bfloat16, acc_dtype, K, device)
    assert order is not None
    rates, coeffs = fmem.fit_expsum(40, 0.15, K)
    compiled = jax.jit(
        lambda g, a, p, s: kfu.expsum_apply(
            g, a, p, s, rates=rates, coeffs=coeffs, alpha=0.02, beta=0.008,
            order=order), donate_argnums=(1, 2)).lower(
        _sds(shape, jnp.bfloat16, one_chip),
        _sds((K,) + shape, acc_dtype, one_chip),
        _sds(shape, jnp.bfloat16, one_chip),
        _sds((), jnp.float32, one_chip)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    moved = [ln for ln in hlo.splitlines()
             if re.search(r" (copy|transpose)\(", ln) and "[]" not in
             ln.split("=", 1)[1].split(" ")[1]]
    assert not moved, moved


_STEPS = {}


def _compiled_step(one_chip, use_kernel):
    """The benchmark cell's step at chip_smoke.py's cut (metrics off, state
    donated), compiled once per test module; returns (compiled, abstract
    state)."""
    if use_kernel not in _STEPS:
        trainer = build_trainer(**CHIP_TRAIN, use_kernel=use_kernel)
        state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                             abstract_train_state(trainer.cfg, trainer.tc,
                                                  trainer.n_agents))
        A, B, S = (CHIP_TRAIN[k] for k in ("agents", "batch_per_agent",
                                           "seq"))
        batch = {k: _sds((A, B, S), jnp.int32, one_chip)
                 for k in ("tokens", "labels")}
        with jax.default_device(_device(one_chip)):
            lowered = trainer.step_fn.lower(state, batch)
        _STEPS[use_kernel] = (lowered.compile(), state)
    return _STEPS[use_kernel]


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "fused"])
def test_one_chip_train_step_fits(one_chip, use_kernel):
    """The cell's step (state donated) leaves at least HEADROOM_BYTES of the
    chip free.  The exp-sum update is the fused kernel on the TPU whatever
    ``use_kernel`` says: that flag is the exact mode's."""
    compiled, state = _compiled_step(one_chip, use_kernel)
    mem = compiled.memory_analysis()
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes      # donated in place
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used <= HBM_BYTES - HEADROOM_BYTES, used / 2 ** 30
    assert "tpu_custom_call" in compiled.as_text()


def _entry(hlo: str) -> list:
    """The ENTRY computation's instructions as (name, shape text, rest):
    rest begins with the opcode."""
    out = []
    for ln in hlo[hlo.index("\nENTRY "):].splitlines()[1:]:
        if ln.startswith("}"):
            break
        m = re.match(r"\s+(?:ROOT )?%(\S+) = (.*)$", ln)
        if not m:
            continue
        rhs, depth, end = m.group(2), 0, m.group(2).find(" ")
        if rhs.startswith("("):               # a tuple shape holds spaces
            for end, ch in enumerate(rhs, 1):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
        out.append((m.group(1), rhs[:end], rhs[end:].lstrip()))
    return out


def _moves_once(kind: str, rest: str) -> bool:
    """Operations on the way to the kernel that read each byte once: a
    bitcast (moves nothing), and XLA's prefetch of an operand into VMEM,
    async slices joined by a ConcatBitcast."""
    return kind in ("bitcast", "slice-start", "slice-done") or (
        kind == "custom-call" and 'custom_call_target="ConcatBitcast"' in rest)


def test_one_chip_step_reads_each_accumulator_once(one_chip):
    """In the compiled one-chip step, every tiled leaf's accumulators are
    an operand of one operation only, the kernel's custom call (reached
    through bitcasts, which move no bytes): no copy, transpose or second
    fusion reads them."""
    compiled, state = _compiled_step(one_chip, False)
    K = CHIP_TRAIN["K"]
    tiled = {"bf16[" + ",".join(map(str, (K,) + p.shape)) + "]"
             for p in jax.tree.leaves(state.params)
             if kfu.expsum_order(p.shape, p.dtype, jnp.bfloat16, K,
                                 _device(one_chip)) is not None}
    assert len(tiled) >= 5
    entry = _entry(compiled.as_text())
    users = {}
    for name, _, rest in entry:
        for op in re.findall(r"%([\w.\-]+)", rest.split(", metadata")[0]):
            users.setdefault(op, []).append(name)
    kind = {name: re.match(r"([\w\-]+)\(", rest).group(1)
            for name, _, rest in entry if re.match(r"[\w\-]+\(", rest)}
    text = {name: rest for name, _, rest in entry}
    found = 0
    for name, shape, rest in entry:
        if not (rest.startswith("parameter(") and shape.split("{")[0]
                in tiled):
            continue
        found += 1
        frontier, readers = [name], set()
        while frontier:
            for u in users.get(frontier.pop(), []):
                if _moves_once(kind[u], text[u]):
                    frontier.append(u)
                else:
                    readers.add(u)
        assert len(readers) == 1, (name, shape, readers)
        reader, = readers
        assert kind[reader] == "custom-call" and \
            "tpu_custom_call" in text[reader], (name, reader)
    assert found >= len(tiled)


@pytest.fixture(scope="module")
def four_chip_step(topology):
    """The four-agent step over a (4, 1) ``data`` x ``model`` mesh of the
    2x2 chips, state donated: (compiled text, abstract state)."""
    mesh = Mesh(np.array(topology.devices).reshape(4, 1), ("data", "model"))
    trainer = build_trainer(**FOUR_CHIP_TRAIN, mesh=mesh)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_train_state(trainer.cfg, trainer.tc, trainer.n_agents),
        trainer.state_shardings)
    A, B, S = (FOUR_CHIP_TRAIN[k] for k in ("agents", "batch_per_agent",
                                            "seq"))
    rows = NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    batch = {k: jax.ShapeDtypeStruct((A, B, S), jnp.int32, sharding=rows)
             for k in ("tokens", "labels")}
    return trainer.step_fn.lower(state, batch).compile().as_text(), state


def test_four_chip_update_adds_no_collective(four_chip_step):
    """The fused update runs per shard, so inside ``frodo.update`` the only
    collective is the clip norm's scalar all-reduce (which XLA may combine
    with other scalars' into one all-reduce of a tuple)."""
    hlo, _ = four_chip_step
    assert "tpu_custom_call" in hlo
    entry = _entry(hlo)
    update = [(shape, rest) for _, shape, rest in entry
              if "frodo.update" in rest]
    gathers = [rest for _, rest in update if rest.startswith("all-gather")]
    reduces = [shape for shape, rest in update
               if re.match(r"all-reduce(-start)?\(", rest)]
    assert not gathers, gathers
    assert reduces and all(re.findall(r"\w+\[[\d,]*\]", r) and all(
        a == "f32[]" for a in re.findall(r"\w+\[[\d,]*\]", r))
        for r in reduces), reduces


def _elements(shape: str) -> int:
    return int(np.prod([int(n) for n in re.findall(
        r"\d+", shape.split("]")[0].split("[")[-1])]))


def test_four_chip_mix_moves_bf16(four_chip_step):
    """Inside ``consensus.*`` each parameter leaf crosses the links as an
    all-to-all and an all-gather of bf16 (on the CPU XLA widens bf16
    collectives to f32, so only this compile shows the wire dtype), each
    named after its opcode, and no all-reduce of a parameter's size is
    left.  For the matrices (a million parameters or more a chip), no copy
    or transpose feeds a collective or reads its result, and the chunks
    that the all-to-all brings are read once, by fusions that sum them in
    float32."""
    hlo, state = four_chip_step
    entry = _entry(hlo)
    text = {name: rest for name, _, rest in entry}
    kind = {name: re.match(r"[\w\-]+", rest).group(0)
            for name, _, rest in entry}
    users = {}
    for name, _, rest in entry:
        for op in re.findall(r"%([\w.\-]+)", rest.split(", metadata")[0]):
            users.setdefault(op, []).append(name)
    mix = [(name, shape) for name, shape, rest in entry
           if "consensus." in rest]
    wire = [(name, shape) for name, shape in mix
            if kind[name].startswith(("all-to-all", "all-gather"))]
    leaves = jax.tree.leaves(state.params)
    assert len(wire) == 2 * len(leaves), wire
    assert all(shape.startswith("bf16[") for _, shape in wire), wire
    # a device trace names an operation by its instruction: each of the
    # mix's collectives is named after its opcode
    assert all(name.startswith(kind[name]) for name, _ in wire), wire
    smallest = min(p.size // p.shape[0] for p in leaves)
    reduces = [shape for name, shape in mix
               if kind[name].startswith("all-reduce")
               and _elements(shape) >= smallest]
    assert not reduces, reduces
    fused = {head.group(1): head.group(2) for head in re.finditer(
        r"\n%(\S+) [^\n]*\{\n(.*?)\n\}", hlo, re.S)}
    matrices = [(name, shape) for name, shape in wire
                if _elements(shape) >= 2 ** 20]
    assert len(matrices) >= 2 * 5, wire
    for name, _ in matrices:
        near = re.findall(r"%([\w.\-]+)", text[name].split(", metadata")[0])
        near += users.get(name, [])
        assert not [n for n in near
                    if kind[n] in ("copy", "transpose")], (name, near)
        if not kind[name].startswith("all-to-all"):
            continue
        frontier, readers = [name], []
        while frontier:
            for u in users.get(frontier.pop(), []):
                if kind[u] in ("bitcast", "slice-start", "slice-done") or (
                        'custom_call_target="ConcatBitcast"' in text[u]):
                    frontier.append(u)
                else:
                    readers.append(u)
        assert readers and all(kind[u] == "fusion" for u in readers), \
            (name, readers)
        for u in readers:
            body = fused[re.search(r"calls=%([\w.\-]+)", text[u]).group(1)]
            assert re.search(r"= f32\[[^\n]* reduce\(", body), (name, body)


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
