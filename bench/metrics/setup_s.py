"""Process start to the window's start: JAX and device start-up, the
compile or the cache load, and one warm-up sweep cell (host clock)."""


def read(m):
    return m.setup_s
