"""The yardstick's arithmetic: peaks, model FLOPs tied to the program's
parameter count, kernel bytes tied to the stack's size."""
import math

import jax
import jax.numpy as jnp
import pytest

from harness import costs, program
from harness.cells import find_cell

CELLS = ("smollm-360m.nnm_cwtm.n4", "whisper-base.nnm_cwtm.n4")


def _program_leaves(cell) -> dict:
    from repro.models import build_model
    model = build_model(program.program_config(cell.config))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return {jax.tree_util.keystr(p): x.shape for p, x in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}


@pytest.mark.parametrize("name", CELLS)
def test_reference_holds_the_programs_leaves(name):
    cell = find_cell(name)
    specs = cell.reference.param_specs(cell.config["sizes"])
    assert {p: tuple(s) for p, (s, _) in specs.items()} == {
        p: tuple(s) for p, s in _program_leaves(cell).items()}


@pytest.mark.parametrize("name", CELLS)
def test_model_flops_tie_to_parameter_count(name):
    """Every leaf is either a weight of a product (2 FLOPs per value per
    position, forward) or a norm, bias or lookup (none); together they are
    the program's parameters."""
    cell = find_cell(name)
    sizes, traffic = cell.config["sizes"], cell.traffic
    count = {p: math.prod(s) for p, s in _program_leaves(cell).items()}
    pos = cell.reference.matmul_positions(sizes, traffic)
    others = set(count) - set(pos)
    assert all("ln" in p or "norm" in p or p.endswith("['bi']")
               or p.endswith("['bo']") or p == "['embed']" for p in others)
    rows = traffic["workers"] * traffic["batch"]
    attn = cell.reference.attention_flops_per_row(sizes, traffic)
    flops = costs.model_flops_per_step(cell.reference, sizes, traffic)
    mm = sum(count[p] * pos[p] for p in pos)
    assert flops == pytest.approx(3 * rows * (2 * mm + attn), rel=1e-12)
    n_params = sum(count.values())
    if name.startswith("smollm"):
        # Tied head: every parameter but the norms meets every token once.
        norms = sum(count[p] for p in others)
        assert mm == (n_params - norms) * traffic["seq"]
        assert n_params == 361_821_120
    else:
        on_frames = sum(count[p] for p in pos if pos[p] == traffic["frames"])
        on_tokens = sum(count[p] for p in pos if pos[p] == traffic["seq"])
        assert on_frames + on_tokens + sum(count[p] for p in others) \
            == n_params
        assert mm == on_frames * traffic["frames"] + on_tokens * traffic["seq"]


def test_kernel_bytes_tie_to_the_stack():
    n, widths = 4, [1000, 24, 3]
    stack = jnp.zeros((n, sum(widths)), jnp.float32)
    flops, nbytes = costs.gram_cost(n, widths)
    assert nbytes == stack.nbytes + len(widths) * n * n * 4
    assert flops == 2 * n * n * sum(widths)
    flops, nbytes = costs.mixtrim_cost(n, widths, mix=False)
    assert nbytes == stack.nbytes + sum(widths) * 4 and flops == 0
    assert costs.mixtrim_cost(n, widths, mix=True)[0] == 2 * n * n * 1027


def test_roofline_takes_the_larger_bound():
    peak = costs.peaks("TPU v5 lite")
    assert costs.roofline_s(197e12, 1.0, peak) == pytest.approx(1.0)
    assert costs.roofline_s(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        costs.peaks("cpu")
