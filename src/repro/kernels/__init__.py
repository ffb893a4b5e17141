# Compute hot-spot kernels (paper's tuned units) + the kernel catalog.
# Each <name>/ops.py exposes a declarative KERNEL (KernelDef); the
# catalog discovers them and builds coordinator-ready KernelCompilettes.
# See repro/kernels/catalog.py for the ~20-line recipe to add one.


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Whether Pallas kernels run in interpret mode.

    The one place the choice is made, from the backend: on for the CPU
    backend (where the tests run), never on a TPU, where every kernel is
    compiled by Mosaic. Kernel wrappers pass their ``interpret`` argument
    through, ``None`` meaning this choice; only a compile for a described
    (not attached) chip passes ``interpret=False`` explicitly.
    """
    if interpret is not None:
        return interpret
    import jax

    return jax.default_backend() == "cpu"


from repro.kernels.catalog import (
    KernelCatalog,
    KernelCompilette,
    KernelDef,
    discover_kernels,
    get_catalog,
)

__all__ = [
    "pallas_interpret",
    "KernelCatalog",
    "KernelCompilette",
    "KernelDef",
    "discover_kernels",
    "get_catalog",
]
