"""Mean engine iterations a batch of the window ran: the loop's trip
count, the largest `n_iterations` among the batch's envelopes (a
finished sim's lanes idle until the slowest is done).  A device counter;
repeats exactly for the same streams."""

from lib import served


def read(ctx):
    per = {}
    for j in served.ok_jobs(ctx):
        env = j["envelopes"][0]
        per[env.batch_id] = max(per.get(env.batch_id, 0),
                                int(env.n_iterations))
    if not per:
        return None
    return sum(per.values()) / len(per)
