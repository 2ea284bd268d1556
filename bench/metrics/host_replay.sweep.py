"""Replay's share of the traced window, in percent."""

from spanlib import host_replay as read  # noqa: F401
