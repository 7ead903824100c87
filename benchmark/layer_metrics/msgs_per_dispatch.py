"""Channel/Socket/dispatcher: messages handled per dispatcher wake-up,
over the window."""

UNIT = "msgs"
DRIVERS = ("served_echo",)


def read(ev):
    batches = ev.counters.get("messenger_dispatch_batches", 0.0)
    if not batches:
        return None
    return ev.counters["messenger_dispatch_messages"] / batches
