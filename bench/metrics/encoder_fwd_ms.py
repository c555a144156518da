"""Device time per train step of the encoder's forward, in ms: the operations
traced under the program's ``encoder`` scope and not under ``transpose(``
(``bench/scopes.py``), both views, backbone and projector."""

from bench import scopes


def read(r):
    return scopes.part_ms(r, scopes.ENCODER_FWD)
