"""DLRM configurations: the paper's Table 2 model and MLPerf's DLRM-DCNv2.

- Table 2 (ACCL+ §6, the paper's use case 2): 100 tables of dim 32, one
  id per table, the concat vector (3200) into an FC stack (2048, 512,
  256), 50 GB of embeddings. `interaction="concat"`, no dense input.
- DLRM-DCNv2 as MLPerf runs it on Criteo 1TB (MLPerf Training
  `recommendation_v2/torchrec_dlrm`, MLPerf Inference
  `recommendation/dlrm_v2`; DCN V2, arXiv:2008.13535; DLRM,
  arXiv:1906.00091): 26 tables of dim 128 with their own row counts,
  sum-pooled multi-hot bags, 13 dense features through a bottom MLP, a
  low-rank cross network over the bottom output and the pooled bags,
  then the top MLP. `interaction="dcnv2"`.

`fc_dims` are the top (over-arch) MLP's hidden widths in both; its last
layer maps to `out_dim` logits with no activation.
"""
import dataclasses

INTERACTIONS = ("concat", "dcnv2")


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    n_tables: int = 100
    emb_dim: int = 32            # 3200 / 100 lookups
    rows_per_table: int = 4_000_000   # ~51 GB total at fp32 x 32-dim
    dense_features: int = 0      # width of the dense input (bottom MLP's)
    fc_dims: tuple = (2048, 512, 256)
    out_dim: int = 1
    table_rows: tuple = ()       # rows of each table; () = rows_per_table
    multi_hot: tuple = ()        # ids per bag of each table; () = 1 each
    bottom_dims: tuple = ()      # bottom MLP widths, ReLU after each
    interaction: str = "concat"
    dcn_layers: int = 0          # low-rank cross layers (dcnv2)
    dcn_rank: int = 0

    def __post_init__(self):
        t = self.n_tables
        for name in ("table_rows", "multi_hot"):
            v = getattr(self, name)
            if v and len(v) != t:
                raise ValueError(f"{name} has {len(v)} entries for {t} "
                                 f"tables")
        if self.interaction not in INTERACTIONS:
            raise ValueError(f"interaction {self.interaction!r} is none of "
                             f"{INTERACTIONS}")
        if self.interaction == "concat":
            # the paper's model: one id per table of equal rows, no dense
            # input; its lookup and checkerboard FC1 take nothing else
            if (self.dense_features or self.bottom_dims or self.dcn_layers
                    or set(self.hots) != {1}
                    or set(self.rows) != {self.rows_per_table}):
                raise ValueError("the concat interaction takes one id per "
                                 "table of rows_per_table rows and no "
                                 "dense input")
        else:
            if self.dense_features < 1 or not self.bottom_dims:
                raise ValueError("dcnv2 needs dense features and a bottom "
                                 "MLP")
            if self.bottom_dims[-1] != self.emb_dim:
                raise ValueError("the bottom MLP must end at emb_dim, the "
                                 "width of each pooled bag")
            if self.dcn_layers < 1 or self.dcn_rank < 1:
                raise ValueError("dcnv2 needs dcn_layers and dcn_rank")

    @property
    def rows(self) -> tuple:
        """Rows of each table."""
        return tuple(self.table_rows) or (self.rows_per_table,) * \
            self.n_tables

    @property
    def hots(self) -> tuple:
        """Ids per bag of each table."""
        return tuple(self.multi_hot) or (1,) * self.n_tables

    @property
    def ids_per_query(self) -> int:
        return sum(self.hots)

    @property
    def interaction_dim(self) -> int:
        """Width of the vector the interaction reads: the bags (and, for
        dcnv2, the bottom MLP's output) side by side."""
        return (self.n_tables + (self.interaction == "dcnv2")) * self.emb_dim


CONFIG = DLRMConfig()


def reduced() -> DLRMConfig:
    return DLRMConfig(n_tables=8, emb_dim=16, rows_per_table=1000,
                      fc_dims=(64, 32), out_dim=1)


# MLPerf DLRM-DCNv2, Criteo 1TB multi-hot (torchrec_dlrm's defaults:
# --num_embeddings_per_feature, --multi_hot_sizes, --dense_arch_layer_sizes,
# --over_arch_layer_sizes, --interaction_type=dcn, --dcn_num_layers,
# --dcn_low_rank_dim).
CRITEO_1TB_ROWS = (
    40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63, 40000000,
    3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14, 40000000, 40000000,
    40000000, 590152, 12973, 108, 36)
CRITEO_1TB_MULTI_HOT = (
    3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27,
    10, 3, 1, 1)


def dlrm_v2() -> DLRMConfig:
    """DLRM-DCNv2 at its published sizes: 204,184,588 rows, 104.5 GB."""
    return DLRMConfig(n_tables=26, emb_dim=128, rows_per_table=40_000_000,
                      dense_features=13, fc_dims=(1024, 1024, 512, 256),
                      out_dim=1, table_rows=CRITEO_1TB_ROWS,
                      multi_hot=CRITEO_1TB_MULTI_HOT,
                      bottom_dims=(512, 256, 128), interaction="dcnv2",
                      dcn_layers=3, dcn_rank=512)


def reduced_v2() -> DLRMConfig:
    """DLRM-DCNv2's structure at a size the CPU tests hold: ragged tables
    (some on one shard of four, some on all), bags of 1 to 5 ids."""
    return DLRMConfig(n_tables=5, emb_dim=8, rows_per_table=1000,
                      dense_features=13, fc_dims=(16, 8), out_dim=1,
                      table_rows=(1000, 3, 130, 600, 40),
                      multi_hot=(3, 1, 2, 5, 1), bottom_dims=(16, 8),
                      interaction="dcnv2", dcn_layers=2, dcn_rank=4)
