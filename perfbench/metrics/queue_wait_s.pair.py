"""Layer: service. Mean of the window jobs' manifest `queue_wait_s`:
admission to the start of a worker. Near 0 says the two jobs were admitted
side by side; a prove's length says the second waited for the first."""
from harness import readers


def read(ctx):
    return readers.mean([s.manifest.get("queue_wait_s")
                         for s in readers.served(ctx)])
