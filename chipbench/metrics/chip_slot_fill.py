"""Share of the fused chunk programs' chip slots that hold a real chip,
in percent: every row admitted to the chunk lanes (``service/server.py``
``PricingService._admit``) is ``S`` systems of ``max_chips`` slots, of
which its own chips fill ``n`` (an IO die counted):
``100 * service_chunk_chips / service_chunk_chip_slots`` from the
program's counters, over the run.  Nothing where no row was admitted or
the program lacks the counters."""


def read(ctx):
    from repro.obs.registry import REGISTRY

    chips = REGISTRY.get("service_chunk_chips")
    slots = REGISTRY.get("service_chunk_chip_slots")
    if chips is None or slots is None or not slots.get():
        return None
    return 100.0 * chips.get() / slots.get()
