"""95th percentile (nearest rank) of submit() to result() on the client's
clock over the window's answered Q6 requests, in milliseconds
(server.throughput): what the short query waits beside the other stream's
join or aggregate."""

import math


def read(run):
    took = sorted(r.t_done - r.t_submit for r in run["requests"]
                  if r.q == "q6")
    if not took:
        return None
    return 1e3 * took[max(0, math.ceil(len(took) * 0.95) - 1)]
