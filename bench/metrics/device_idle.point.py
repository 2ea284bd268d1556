"""Device idle share of the traced window, in percent."""

from layerlib import idle_percent as read  # noqa: F401
