"""One module per kind of service the benchmark drives, found by name.

A configuration's ``service`` names its module: ``"service":
"embed_gather"`` is ``kinds/embed_gather.py``.  ``spec.load_kind`` loads
it by path, as ``spec.metric_reader`` loads a metric's reader, so a new
kind is a new file and no existing one changes.

What a kind module defines, and what ``run.py``, ``control.py`` and
``check.py`` use of it:

- ``KIND`` and ``ARRIVALS``: the ``kind`` of the traffic files it reads
  and the ``arrival``s it drives (today ``"closed"``).
- ``Cell(config, traffic, seed, spans, devices)``: builds the cell's data
  from the seed and the system under test through the program's public
  entry points, on ``devices`` (the chips ``run.start_chip`` found, as
  many as the cell asks for).  It refuses traffic of another kind or
  arrival (``cells.refuse_unless``).  Its instances have
  - ``concurrency`` (requests in flight), ``done`` (every ``Retired``, in
    retirement order) and ``setup_log`` (named set-up times, logged);
  - ``counters()`` -> ``cells.Counters``; ``fill()``, which puts
    ``concurrency`` requests in flight; ``step(resubmit=True)``, one
    scheduler round, returning a progress count (0: nothing moved);
    ``warm_bursts()``, which drives every batch shape the window can form;
    ``drain()``; ``in_flight()``; ``release()``, which drops the system
    under test and keeps what the check needs.
  ``cells.ClosedLoop`` gives ``counters``, ``fill`` and ``drain`` for a
  loop over a ``repro.core.Cluster`` (``self.cluster``).
- ``check(cell, records, missing, control=False) -> (checks, failed)``:
  compares ``records`` (the ``Retired`` entries from the window's start)
  with a plain reference that imports nothing of the program, and
  ``missing`` requests due that never retired.  ``checks`` is ``{name:
  (value, limit)}``, ``failed`` the requests that failed.  With
  ``control`` the reference, one step below the configuration's stated
  guarantee, answers in the program's place; it has to fail.

A new deployment adds files only:

- ``bench/kinds/<service>.py``, only if its service is new;
- ``bench/configs/<config>.json``, whose ``service`` names the module;
- ``bench/traffic/<mix>.json``, whose ``kind`` and ``arrival`` the module
  drives;
- its entries in ``BENCHMARK.json``: the configuration, its cells, and any
  new metric with its reader ``bench/metrics/<name>.py``.
"""
