"""``device_idle_pct`` where the end-to-end metric is ``scan_query_s``
(session.q6)."""

from readers import device_idle_pct as read  # noqa: F401
