"""Core library: the paper's contribution (MobiHoc'23, Parasnis et al.).

Connectivity-aware semi-decentralized federated learning over time-varying
directed D2D cluster networks:

* ``graphs``    -- digraph primitives + the deprecated ``D2DNetwork``
  shim (graph *generation* lives in the ``repro.topology`` registry)
* ``adjacency`` -- equal-neighbor column-stochastic matrices (Sec. 3.2)
* ``bounds``    -- singular-value bounds & connectivity factor (Sec. 3.3, 5)
* ``sampling``  -- the m(t) threshold rule + proportional sampling (Sec. 3.3)
* ``rounds``    -- the jitted Algorithm-1 round (Sec. 3, Alg. 1)
* ``server``    -- PS orchestration: Alg. 1, FedAvg, COLREL (Sec. 6)
* ``theory``    -- Theorem 4.5 rate bound and step-size schedule (Sec. 4)
* ``metrics``   -- D2S/D2D energy accounting (Sec. 6.2)
"""

from .adjacency import (block_diagonal, equal_neighbor_matrix,
                        is_column_stochastic, network_matrix,
                        network_matrix_sparse, phi_ell,
                        top_singular_values)
from .bounds import (connectivity_factor, exact_phi_ell, psi_ell_from_stats,
                     psi_general, psi_regular, psi_total)
from .graphs import (ClusterGraph, D2DNetwork, DegreeStats,
                     SparseClusterGraph, delete_edge_fraction,
                     degree_stats, degree_stats_from_arrays,
                     ensure_positive_out_degree, k_regular_digraph)
from .sparse import SparseA, SparseAseq, ell_from_dense
from .metrics import CommLedger, count_d2d_transmissions
from .rounds import (MIXING_BACKENDS, client_deltas, global_update,
                     local_sgd, make_round_fn, make_scanned_rounds,
                     mix_deltas)
from .sampling import min_clients, sample_clients
from .server import FederatedServer, History, RoundRecord, ServerConfig
from .theory import TheoryConstants, eta_schedule, gap_bound, t1_threshold

__all__ = [name for name in dir() if not name.startswith("_")]
