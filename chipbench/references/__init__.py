"""Plain references of the decoders the benchmark's configurations run,
one module per architecture, named by a configuration's ``reference``."""
