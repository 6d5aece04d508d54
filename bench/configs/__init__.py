"""One file pair per configuration: ``<name>.json`` (sizes as run) and
``<name>.py`` (its plain reference model, inputs and FLOP count)."""
