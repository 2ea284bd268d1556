"""Kernel calls' host-side share of the traced window, in percent."""

from spanlib import host_kernel_call as read  # noqa: F401
