"""``plan_ms`` where the end-to-end metric is ``query_s``
(session.q1, session.q14)."""

from book_readers import plan_ms as read  # noqa: F401
