"""Codegen runs per request of the traced window."""

from spanlib import codegen_per_request as read  # noqa: F401
