"""Process start to the first request of the ramp: loading or building the
index, packing it, compiling or loading every served program, and making
the traffic."""


def read(m):
    return m.setup_s
