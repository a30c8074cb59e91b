"""Program span: of the positions the traced window's decode rows scored in a
full layer, the share they then attended: ``index_positions_selected`` over
``index_positions_scored``, summed over the window's own ``engine.counts``
events, percent. 100 while no row has passed ``index_topk`` positions."""

from benchmark import sparse_latent

read = sparse_latent.on_window(sparse_latent.selected_share)
