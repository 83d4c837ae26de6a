"""Mean milliseconds per window step from the batch's transfer call to the
return of the jitted step, before the loss is read."""


def read(r):
    return sum(r.dispatch_s) / len(r.dispatch_s) * 1e3
