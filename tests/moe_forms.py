"""``blocks._moe_mlp`` through both forms of its experts, for the model
families' tests: the tiled path (``_expert_mix``, what a CPU run takes) and
the kernels that take their rows by table (``_expert_rows``, a decode
step's on a TPU), which are interpreted here."""

import jax
import numpy as np

from polyrl_tpu.models import blocks
from polyrl_tpu.ops import grouped_matmul


def assert_both_forms_agree(monkeypatch, cfg, x, lp, valid=None, layer=None,
                            route=None, atol=2e-6):
    """The block's result and load through the tiled path and by table, to
    float32 rounding (a token's k terms are added in another order);
    returns the tiled path's result."""
    def block(x):
        return blocks._moe_mlp(cfg, x, lp, valid, layer, route)

    def kernels():      # a function of its own: a trace is kept by function
        return str(jax.make_jaxpr(lambda x: block(x))(x)).count("pallas_call")

    want, load = block(x)
    assert kernels() == 0
    with monkeypatch.context() as m:
        m.setattr(grouped_matmul, "in_kernel", grouped_matmul.rows_by_table)
        assert kernels() == 2
        got, load_by_table = block(x)
    assert float(np.abs(np.asarray(want)).max()) > 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=atol)
    assert load_by_table.tolist() == load.tolist()
    return want
