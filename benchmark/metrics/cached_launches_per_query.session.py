"""``cached_launches_per_query`` where the end-to-end metric is ``query_s``
(session.q1, session.q14)."""

from book_readers import cached_launches_per_query as read  # noqa: F401
