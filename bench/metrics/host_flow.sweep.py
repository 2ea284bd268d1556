"""Flow compile's share of the traced window, in percent."""

from spanlib import host_flow as read  # noqa: F401
