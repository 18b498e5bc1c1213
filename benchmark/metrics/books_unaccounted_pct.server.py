"""``books_unaccounted_pct`` of the served streams (server.throughput): the
check that a query's book still adds up beside another query in flight."""

from book_readers import books_unaccounted_pct as read  # noqa: F401
