"""Arrival processes, one module each, named by a traffic mix's
``arrivals.kind`` (see :func:`benchkit.traffic.arrival_process`)."""
