"""Peak device memory in GiB: on the fullest device, the larger of the
allocator's peak after the window and the compiled step's own footprint
(arguments + outputs - aliased + temporaries)."""


def read(r):
    return r.peak_hbm_bytes / 2**30
