"""The least time the chip needs for one step's latent attention (scores
over the 192 numbers of a key, weighted values over the 128 of a value,
causal as causal: this configuration's ``counts.attention_step_work``), over
the traced time of the three flash kernels by their names (``flash_fwd``,
``flash_dq``, ``flash_dkv``): ``banded_flash_roofline_share``'s reduction
under the name this cell reports it by (that entry's list of cells is an
accepted one). The count is of the mathematics: lanes a kernel pads read as
a lower share, not as more work."""

from metrics.banded_flash_roofline_share import read  # noqa: F401
