"""Decode prep's share of the traced window, in percent."""

from spanlib import host_prep as read  # noqa: F401
