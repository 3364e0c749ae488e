"""Drivers: how a traffic mix drives the system under test.

A mix (``benchmark/traffic/<mix>.json``) names its driver;
``run.py`` imports ``benchmark.drivers.<driver>``, which defines:

  programs(config, mix) -> [str]   the compiled programs set-up warms
  prepare(config, mix, seed, workers)
                                   starts making the fixtures from the
                                   seed; ``.result()`` waits for them,
                                   ``.close()`` stops the workers
  Driver(config, mix, fixtures)    with ``upload()`` (device state set-up
                                   makes), ``call(k)`` (one unit of
                                   work: ``[(item, decision)]``) and
                                   ``window(seconds, k)`` (drives the
                                   entry for the window; a
                                   ``loops.Window``)
  judge(fixtures, decisions, workers) -> {check: count}
                                   every decision against the plain
                                   reference, after the window; each
                                   count has the limit 0

A new kind of traffic (another generator, loop or judge) is a new
driver module beside these, and needs no edit to ``run.py``.
"""
