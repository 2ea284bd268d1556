"""The validation step's share of the chip's int8 peak, in percent."""

from layerlib import func_mfu as read  # noqa: F401
