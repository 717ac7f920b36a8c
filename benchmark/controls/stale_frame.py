"""Broken timed path: every fourth submit encodes the frame before it again
(a step that returns its state unchanged).  The stream stays a sound one,
and the client gets a picture that it has already shown.

The break sits at submit and not at collect: a fragment handed out twice
takes a frame out of the P chain, the decoder then drops pictures as it
likes, pictures and fragments no longer pair, and a run can end with no
frame in its window instead of with ``correct`` false (one in four of the
test's did)."""


def apply(session) -> None:
    submit = session.encoder.encode_submit
    state = {"n": 0, "last": None}

    def stale(rgb):
        state["n"] += 1
        if state["n"] % 4 == 0 and state["last"] is not None:
            return submit(state["last"])
        state["last"] = rgb.copy()
        return submit(rgb)

    session.encoder.encode_submit = stale
