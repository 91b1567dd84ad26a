"""The platform the kernels run on, asked in one place: the dispatch layer
(``ops``), the Pallas kernels' interpret default and the roofline peaks
(``launch/roofline.py``) all read it here."""
from __future__ import annotations

import jax


def device():
    """The device JAX places work on by default."""
    return jax.devices()[0]


def on_tpu() -> bool:
    """Whether that device is a TPU."""
    return device().platform == "tpu"


def default_interpret(interpret: bool | None) -> bool:
    """Pallas interpret mode: off on a TPU, on everywhere else, unless the
    caller says which."""
    return (not on_tpu()) if interpret is None else bool(interpret)
