"""95th percentile over all requests completed in the window of the time
from the request's batch entering ``greedy_generate`` to its return."""
from bench import stats


def read(run, result):
    return stats.percentile(result["window"]["latencies"], 95)
