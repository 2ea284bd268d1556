"""Device idle time inside no program span, in percent of the window."""

from spanlib import idle_unattributed as read  # noqa: F401
