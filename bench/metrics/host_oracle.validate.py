"""The numpy oracle's share of the traced window, in percent."""

from spanlib import host_oracle as read  # noqa: F401
