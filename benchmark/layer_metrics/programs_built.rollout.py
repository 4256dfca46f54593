"""Programs JAX traced and lowered inside the window (``jax.monitoring``
``jaxpr_to_mlir_module`` events): a shape the set-up did not cover. Each
costs a cache read of seconds, or a compile of tens of seconds that also
makes the run incorrect. Should be 0. Layer: CBEngine loop."""


def read(obs):
    return obs["checks"].get("traced_in_window")
