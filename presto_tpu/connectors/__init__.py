from presto_tpu.connectors.tpch import (TPCH_SCHEMA, TpchConnector,
                                        TpchTextOnReadConnector)
from presto_tpu.connectors.tpcds import TPCDS_SCHEMA, TpcdsConnector
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.parquet import ParquetConnector

__all__ = ["TPCH_SCHEMA", "TpchConnector", "TpchTextOnReadConnector",
           "TPCDS_SCHEMA", "TpcdsConnector", "MemoryConnector",
           "ParquetConnector"]
