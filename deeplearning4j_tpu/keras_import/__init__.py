"""Keras .h5 model import.

Reference parity: deeplearning4j-modelimport (`KerasModelImport.java:48-192`,
`KerasModel.java`, `KerasLayer.java`, `Hdf5Archive.java`) — parse the Keras
JSON config stored in the HDF5 file, map layers to native configs
(Sequential → MultiLayerNetwork, functional Model → ComputationGraph), and
copy weights with convention transposes. The reference reads HDF5 through
JavaCPP JNI bindings; here h5py plays that role.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.keras_import"):
    from deeplearning4j_tpu.keras_import.importer import (
        KerasModelImport, import_keras_configuration,
        import_keras_model_and_weights,
    )
    from deeplearning4j_tpu.keras_import.h5 import Hdf5Archive

__all__ = ["KerasModelImport", "import_keras_configuration",
           "import_keras_model_and_weights", "Hdf5Archive"]
