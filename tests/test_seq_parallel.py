"""Sequence parallelism (ctx -> 'model'): dry-run one train_4k cell with
the override and pin, at lowering level, that the residual-stream carries
actually pick up the model-axis sharding (Megatron-SP style). Closes the
ROADMAP item that shipped the override without ever exercising it."""
import jax
import pytest

from repro.configs import get_config
from repro.dist import sharding as sh
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_cell, lower_cell

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs >= 8 (virtual) devices")

# mesh (data=2, model=4): the ("batch", "ctx", "embed") residual constraint
# resolves to P("data", "model", None), which lowers to this Shardy sharding
SEQ_SHARDED = '[{"data"}, {"model"}, {}]>'


def _lower_train4k(overrides):
    cfg = get_config("stablelm-1.6b").reduced()
    mesh = make_host_mesh(2, 4)
    assert dict(mesh.shape) == {"data": 2, "model": 4}
    with sh.use_rules(mesh, overrides) as rs:
        cell = build_cell(cfg, "train_4k", rs, remat="none")
        return lower_cell(cell, mesh, overrides)


@multi_device
def test_train4k_ctx_to_model_lowers_sequence_parallel():
    lowered = _lower_train4k({"ctx": "model"})
    text = lowered.as_text()
    assert SEQ_SHARDED in text, (
        "ctx->model override did not shard the residual stream over the "
        "model axis")


@multi_device
def test_train4k_default_keeps_ctx_replicated():
    assert SEQ_SHARDED not in _lower_train4k(None).as_text()
