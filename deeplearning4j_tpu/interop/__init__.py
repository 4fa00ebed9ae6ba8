"""Interop with external checkpoint formats (DL4J zip containers)."""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.interop"):
    from deeplearning4j_tpu.interop.dl4j import (  # noqa: F401
        export_dl4j_model,
        import_dl4j_model,
        read_nd4j_array,
        write_nd4j_array,
    )

__all__ = [
    "export_dl4j_model",
    "import_dl4j_model",
    "read_nd4j_array",
    "write_nd4j_array",
]
