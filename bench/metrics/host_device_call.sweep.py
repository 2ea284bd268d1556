"""Device calls seen from the host, in percent of the traced window."""

from spanlib import host_device_call as read  # noqa: F401
