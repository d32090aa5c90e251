"""h2o-danube-1.8b [dense] — llama+mistral mix with sliding-window attention.
Source: arXiv:2401.16818."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="h2o-danube-1.8b", family="dense",
    source="arXiv:2401.16818",
    n_layers=24, d_model=2560, n_heads=32, n_kv_heads=8,
    d_ff=6912, vocab=32000, attn_type="swa", window=4096,
    activation="silu", gated_mlp=True,
    agent_axes_single=("data",), agent_axes_multi=("pod", "data"),
)

#: One-chip training cut (chip_smoke.py, tests/test_tpu_compile.py): every
#: width as published in arXiv:2401.16818, the depth cut so that two agents'
#: parameters and their bf16 exp-sum memory fit one TPU v5e chip (16 GB),
#: with the state donated.  Each key changed from CONFIG, as
#: (published, here); the layers left out would be further pipeline stages.
reduced = {"n_layers": (24, 2)}

#: ``launch.train.run_training`` / ``build_trainer`` arguments of that cut:
#: 2 agents on a complete graph, seq 2048, batch 1 per agent, exp-sum memory
#: with K=4 bf16 accumulators (the f32, K=8 default does not fit).
CHIP_TRAIN = dict(smoke=False, layers=reduced["n_layers"][1], agents=2,
                  seq=2048, batch_per_agent=1, memory_mode="expsum", K=4,
                  acc_dtype="bfloat16", topology="complete")

#: Four agents over a four-chip host, compared with the same step on one
#: chip, which holds all four agents only at one layer.
FOUR_CHIP_TRAIN = dict(CHIP_TRAIN, layers=1, agents=4)


def smoke_config() -> ModelConfig:
    return CONFIG.replace(n_layers=2, d_model=256, n_heads=8, n_kv_heads=2,
                          d_ff=512, vocab=512, window=64)
