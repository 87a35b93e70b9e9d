"""Window wall time over proofs served in it: what the follower waits for
one proof, request in to verified proof out. All the work over all the time."""


def read(ctx):
    w = ctx["window"]
    return w.wall_s / len(w.ok) if w.ok else None
