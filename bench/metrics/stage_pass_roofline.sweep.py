"""The stage pass's share of its memory roofline, in percent."""

from layerlib import stage_pass_roofline as read  # noqa: F401
