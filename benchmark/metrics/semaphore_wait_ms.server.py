"""Median milliseconds a served query waited for a device permit: the
``semaphore_wait`` bucket of the ledgers inside the window's answered
requests (server.throughput).  Nothing where the program publishes no
ledger."""

from book_readers import _bucket_ms


def read(run):
    return _bucket_ms(run, "semaphore_wait")
