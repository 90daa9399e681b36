"""Lanes that carried a request over lanes run, over the distinct batches
that answered the window's requests: sum(occupancy) / sum(batch_size).
Each answer reports its batch's occupancy and size; a batch of occupancy
o answers o requests, so the sums over batches are sums over answers of
1 and of batch_size / occupancy."""


def read(outcome, reduced, ctx):
    batches = [a.body["batch"] for a in outcome.answers if a.ok]
    if not batches:
        return None
    lanes_run = sum(b["batch_size"] / b["occupancy"] for b in batches)
    return 100.0 * len(batches) / lanes_run
