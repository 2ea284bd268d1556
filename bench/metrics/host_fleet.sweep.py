"""The fleet's own share of the traced window, in percent."""

from spanlib import host_fleet as read  # noqa: F401
