"""Exposed communication, ms per traced step: collective time (operations
and in-flight asynchronous collectives) during which no other operation ran
on the device, mean over the devices.  The step's only collectives are its
gathers, hop 1, hop 2 and the boundary's reductions (``tests/test_scopes.py``
finds each under the program's ``mics.*`` scopes; on the TPU, hop 1's
reduce-scatters become all-reduces that carry no ``op_name``), so this is
the step's exposed MiCS communication."""


def read(r):
    if r.trace is None:
        return None
    return r.trace["exposed_collective_s"] / r.trace_steps * 1e3
