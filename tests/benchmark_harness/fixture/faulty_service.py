"""A service of the package with one fault planted under the timed path.

Started by the harness in the service's place (a configuration's
``service`` key names this module), with the fault named in
``BENCH_TEST_FAULT`` and the service in ``BENCH_TEST_SERVICE`` (the
detector service where it is not set). Each fault breaks one of the guarantees the
configurations state, where the program produces the answer:

``half_batch``       every ev44 message loses the second half of its events.
``state_unchanged``  the tick's step returns its state as it got it.
``altered_answer``   one bin of every fetched result is off by one.
``altered_frame``    one pixel of every ad00 frame is off by one where it is decoded.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np


def plant(fault: str) -> None:
    if fault == "half_batch":
        from esslivedata_tpu.kafka import wire

        sound = wire.decode_ev44

        def half(buf):
            ev = sound(buf)
            n = ev.pixel_id.size // 2
            return dataclasses.replace(
                ev, time_of_flight=ev.time_of_flight[:n], pixel_id=ev.pixel_id[:n]
            )

        wire.decode_ev44 = half
    elif fault == "state_unchanged":
        from esslivedata_tpu.ops.histogram import EventHistogrammer

        EventHistogrammer.tick_step = lambda self, states, *staged: tuple(states)
    elif fault == "altered_answer":
        import jax

        sound_get = jax.device_get

        def altered(tree):
            out = sound_get(tree)
            packed = out[0] if isinstance(out, tuple) and len(out) == 2 else None
            if isinstance(packed, np.ndarray) and packed.ndim == 1 and packed.size > 8:
                packed = np.array(packed)
                packed[-1] += 1  # the last TOA bin of the last spectrum packed
                return (packed, out[1])
            return out

        jax.device_get = altered
    elif fault == "altered_frame":
        from esslivedata_tpu.kafka import wire

        sound_ad00 = wire.decode_ad00

        def altered_frame(buf):
            image = sound_ad00(buf)
            data = np.array(image.data)
            data[0, 0] += 1
            return dataclasses.replace(image, data=data)

        wire.decode_ad00 = altered_frame
    else:
        raise SystemExit(f"faulty_service: unknown fault {fault!r}")


if __name__ == "__main__":
    import importlib

    plant(os.environ["BENCH_TEST_FAULT"])
    service = os.environ.get("BENCH_TEST_SERVICE", "detector_data")
    main = importlib.import_module(f"esslivedata_tpu.services.{service}").main
    raise SystemExit(main(sys.argv[1:]))
