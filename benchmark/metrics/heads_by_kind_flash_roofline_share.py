"""The least time the chip needs for one step's attention with each layer's
query heads as that layer has them and its mask counted as what it is (a
window as a window, a causal mask as causal: this configuration's
``counts.attention_step_work``), over the traced time of the three flash
kernels by their names (``flash_fwd``, ``flash_dq``, ``flash_dkv``):
``banded_flash_roofline_share``'s reduction under the name this cell
reports it by (that entry's list of cells is an accepted one)."""

from metrics.banded_flash_roofline_share import read  # noqa: F401
