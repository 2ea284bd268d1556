"""The Pallas-backed forward's own share of the traced window, in percent."""

from spanlib import host_forward as read  # noqa: F401
