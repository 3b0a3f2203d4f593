# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.


def default_interpret() -> bool:
    """Pallas interpret mode for the default backend: compiled Mosaic
    on ``"tpu"``, the interpreter on ``"cpu"`` (tests), and an error on
    anything else — a kernel must never silently run interpreted on an
    accelerator it was not written for."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas kernel path for backend {backend!r}: the kernels "
        "compile for 'tpu' and interpret on 'cpu' only"
    )
