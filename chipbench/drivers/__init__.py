"""Drivers, one per configuration ``driver`` kind: ``serve`` and ``train``.

Each exposes ``run(ctx) -> dict`` (see ``chipbench.harness.Context``)."""
