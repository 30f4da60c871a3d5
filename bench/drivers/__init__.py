"""Traffic drivers: one general generator per kind of traffic.

A traffic mix (``bench/traffic/<mix>.json``) is data: it names its
driver under ``driver`` and gives the driver's parameters.  A driver
module defines ``Driver(run)`` with:

``setup()``        build the system and its inputs from the seed and the
                   configuration, and warm every shape the window uses;
``window()``       drive the measured window, marking its bounds with
                   ``run.window_start()`` / ``run.window_end()``;
``end_to_end()``   the end-to-end metrics of the window, by name;
``release()``      free the program's state before the reference runs;
``check()``        compare what the window produced with the plain
                   reference: ``{name: (value, limit)}``, correct when
                   every value is at most its limit;
``readings(peaks)``what the per-layer readers read (traced runs);
``attempted`` / ``failed``  operations offered and failed in the window.
"""
