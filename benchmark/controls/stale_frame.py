"""Broken timed path: every fourth collect returns the frame before it again
(a step that returns its state unchanged).  The client gets a fragment whose
picture it has already shown."""


def apply(session) -> None:
    collect = session.encoder.encode_collect
    state = {"n": 0, "last": None}

    def stale(token):
        ef = collect(token)
        state["n"] += 1
        if state["n"] % 4 == 0 and state["last"] is not None \
                and not ef.keyframe:
            return state["last"]
        state["last"] = ef
        return ef

    session.encoder.encode_collect = stale
