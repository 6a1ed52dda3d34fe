# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.


def on_tpu() -> bool:
    """Whether the default jax backend is a real TPU, where every Pallas
    kernel compiles natively.  Every kernel wrapper passes
    `interpret=not on_tpu()`: interpret mode is the CPU test path only."""
    import jax
    return jax.default_backend() == "tpu"

