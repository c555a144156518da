"""Device time per train step of the encoder's backward, in ms: the
operations traced under the program's ``encoder`` scope inside
``transpose(`` (``bench/scopes.py``), both views, backbone and projector."""

from bench import scopes


def read(r):
    return scopes.part_ms(r, scopes.ENCODER_BWD)
