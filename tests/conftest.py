import os

# Tests run on the single real CPU device (the dry-run, and ONLY the
# dry-run, uses 512 placeholder devices via its own env line).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from repro import configs


def small_config(cfg: configs.ArchConfig) -> configs.ArchConfig:
    """Reduced config of the same family (assignment: smoke tests)."""
    over = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                d_ff=128, vocab_size=256, head_dim=16)
    if cfg.family == "moe":
        over.update(num_experts=4, experts_per_token=2, moe_d_ff=64)
    if cfg.family == "ssm":
        over.update(num_heads=4, num_kv_heads=4, head_dim=16, ssm_state=16)
    if cfg.family == "hybrid":
        over.update(num_layers=5, attn_every=2, ssm_state=16, num_kv_heads=4)
    if cfg.family == "audio":
        over.update(encoder_layers=2, frontend_len=8, frontend_dim=32)
    if cfg.family == "vlm":
        over.update(frontend_len=4, frontend_dim=32)
    return cfg.scaled(**over)


@pytest.fixture(scope="session")
def clustered_vectors():
    from repro.data import vectors
    return vectors.make_dataset(n=6000, d=24, num_learn=512, num_queries=128,
                                clusters=32, cluster_std=1.2, seed=0)
