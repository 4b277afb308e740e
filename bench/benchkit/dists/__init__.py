"""Length and gap distributions, one module each, named by a
distribution's ``dist`` key (see :func:`benchkit.traffic.stratified`)."""
