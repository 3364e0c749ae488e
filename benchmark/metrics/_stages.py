"""The reader shared by the ``<stage>_ms.<cell>`` metrics: one of the
program's own host stages (``harmony_tpu.prof.stage``), in ms per item
(check or header), the unit of ``kernel_ms.*``.

``run.py`` arms ``prof`` just before the window, in ``--trace 1`` runs
only, so a stage's sum covers the window and the traced slice's call,
and is divided by the items both decided.  A stage never recorded (a
program without it, or a run that never armed ``prof``) reads None.
"""

from __future__ import annotations


def reader(stage: str):
    """A metric's ``read(run)`` for ``prof.stage(stage)``."""

    def read(run):
        from harmony_tpu import prof

        n = run.items + run.traced_items
        s = prof.stage_summary().get(stage)
        if not n or not s or not s["count"]:
            return None
        return 1000 * s["sum_s"] / n

    return read
