"""Share of decode-dispatch slots that produced a token: tokens decoded in
the window (``tokens_generated`` less the first tokens, which prefill
produces, one per ``prefills``) over decode dispatches x
``steps_per_dispatch`` x slots. All deltas of ``stats()``."""


def read(ctx):
    f = ctx.facts
    room = f.get("decode_dispatches", 0) * f.get("steps_per_dispatch", 0) \
        * f.get("slots", 0)
    if not room:
        return None
    return 100.0 * (f["tokens"] - f["prefills"]) / room
