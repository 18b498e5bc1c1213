"""``launch_gap_ms`` where the end-to-end metric is ``query_s``
(session.q1, session.q14)."""

from readers import launch_gap_ms as read  # noqa: F401
