"""``device_idle_pct`` where the end-to-end metric is ``query_s``
(session.q1, session.q14)."""

from readers import device_idle_pct as read  # noqa: F401
