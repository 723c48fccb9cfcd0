"""Where a Pallas kernel runs compiled: ``resolve_interpret``."""
from typing import Optional

import jax

# backends with a compiled Pallas lowering for these kernels; anything
# else (cpu, the gpu triton path we don't target) runs the interpreter
_COMPILED_PALLAS_BACKENDS = ("tpu",)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a kernel's ``interpret`` argument platform-aware.

    ``None`` (the default every kernel should expose) means *interpret
    only when no compiled backend supports the kernel*: on TPU the Pallas
    kernel compiles natively, everywhere else the interpreter is the only
    way to run it.  Passing an explicit bool always wins — tests force
    ``interpret=True`` for determinism, and a TPU user can force the
    interpreter to debug a kernel.

    It reads the default backend, never a traced value, so calling it while
    a jitted caller traces is fine.
    """
    if interpret is None:
        return jax.default_backend() not in _COMPILED_PALLAS_BACKENDS
    return bool(interpret)
