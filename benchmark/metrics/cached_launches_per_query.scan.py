"""``cached_launches_per_query`` where the end-to-end metric is ``scan_query_s``
(session.q6)."""

from book_readers import cached_launches_per_query as read  # noqa: F401
