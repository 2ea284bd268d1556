"""Host finish's share of the traced window, in percent."""

from spanlib import host_finish as read  # noqa: F401
